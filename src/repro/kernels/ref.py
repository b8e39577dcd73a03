"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def conv2d(x: jax.Array, w: jax.Array, s_h: int = 1, s_w: int = 1
           ) -> jax.Array:
    """(C_in, H_in, W_in) x (N, C_in, Hk, Wk) -> (N, H_out, W_out).

    f32 at full precision: left at the default, a TPU runs an f32 conv
    in bf16 passes and the oracle would be no tighter than bf16."""
    out = lax.conv_general_dilated(
        x[None].astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(s_h, s_w), padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)
    return out[0].astype(x.dtype)


def matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32),
                   b.astype(jnp.float32)).astype(a.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: int | None = None) -> jax.Array:
    """Single-position attention: q (G, D), k/v (S, D) -> (G, D).

    ``length`` masks positions >= length (padded KV cache)."""
    scores = jnp.einsum("gd,sd->gs", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    if length is not None:
        pos = jnp.arange(k.shape[0])
        scores = jnp.where(pos[None, :] < length, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("gs,sd->gd", p, v.astype(jnp.float32)).astype(q.dtype)
