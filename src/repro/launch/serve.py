"""Serving launcher: batched prefill + decode loop.

The decode step is the S1 offloading schedule of DESIGN.md §4: resident
queries stream the KV cache block by block (the Pallas flash_decode kernel
on TPU; the sharded jnp path under pjit).  Smoke mode runs a real batched
generation on CPU with the reduced config."""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import registry
from repro.models.common import Axes


class ServeConfigError(ValueError):
    """A serving config that cannot run (non-positive batch/lengths) —
    caught at the entry point instead of surfacing as a shape error deep
    inside jit tracing (or, for ``gen_len=0``, an empty ``np.stack``)."""


def serve(arch: str, *, smoke: bool = True, batch: int = 4,
          prompt_len: int = 32, gen_len: int = 16,
          multi_pod: bool = False, greedy: bool = True):
    if batch < 1 or prompt_len < 1 or gen_len < 1:
        raise ServeConfigError(
            f"batch, prompt_len and gen_len must all be >= 1, got "
            f"batch={batch} prompt_len={prompt_len} gen_len={gen_len}")
    with contextlib.ExitStack() as mesh_ctx:
        if smoke:
            api = registry.get_reduced(arch)
            axes = None
        else:
            api = registry.get(arch)
            mesh = make_production_mesh(multi_pod=multi_pod)
            mesh_ctx.enter_context(jax.set_mesh(mesh))
            axes = Axes.for_mesh(mesh)
        return _serve_loop(api, axes, batch=batch, prompt_len=prompt_len,
                           gen_len=gen_len)


def _serve_loop(api, axes, *, batch, prompt_len, gen_len):
    cfg = api.cfg
    max_len = prompt_len + gen_len

    params = api.init_params(jax.random.key(0), axes)
    rng = np.random.default_rng(0)
    prompts = rng.integers(3, cfg.vocab, size=(batch, prompt_len))

    prefill = jax.jit(lambda p, b: api.prefill_fn(p, b, axes,
                                                  max_len=max_len))
    decode = jax.jit(steps_mod.make_decode_step(api, axes))

    t0 = time.time()
    if cfg.family == "audio":
        frames = jnp.asarray(
            rng.standard_normal((batch, prompt_len, cfg.d_model)),
            jnp.bfloat16)
        logits, cache = prefill(params, {"frames": frames})
        start_pos = 1
    else:
        logits, cache = prefill(params, {"tokens": jnp.asarray(prompts)})
        start_pos = prompt_len
    t_prefill = time.time() - t0

    out_tokens = []
    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for i in range(gen_len):
        out_tokens.append(np.asarray(tok)[:, 0])
        logits, cache = decode(params, cache, tok, jnp.int32(start_pos + i))
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    t_decode = time.time() - t0
    gen = np.stack(out_tokens, axis=1)
    print(f"[serve] batch={batch} prefill {t_prefill:.2f}s, "
          f"{gen_len} decode steps {t_decode:.2f}s "
          f"({t_decode / gen_len * 1e3:.0f} ms/step on CPU)")
    return gen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    args = ap.parse_args(argv)
    gen = serve(args.arch, smoke=args.smoke, batch=args.batch,
                prompt_len=args.prompt_len, gen_len=args.gen_len,
                multi_pod=args.multi_pod)
    print("[serve] generated token matrix shape:", gen.shape)


if __name__ == "__main__":
    main()
