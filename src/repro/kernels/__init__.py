"""Pallas kernels for the offloading plans, their wrappers and oracles."""
import jax


class KernelShapeError(ValueError):
    """Host-side kernel argument/shape contract violation."""


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether a Pallas call runs in interpret mode.

    ``None`` decides from the backend: compiled on a TPU, interpreted on
    anything else.  A chip path passes ``False`` so that it never
    interprets quietly."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
