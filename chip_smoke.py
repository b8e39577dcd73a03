"""Smoke run of the plan -> verify -> emit -> run path on one TPU chip.

Plans ``resnet8`` and ``lenet5`` from their committed configs with the
emitable solver under ``kerncheck.network_budget``, verifies each plan
(``verify=True``) and proves its emitted kernels contract-equivalent
(``kerncheck.check_network``), then runs the whole network through the
emitted Pallas kernels (``execute_network``, compiled, never
interpreted) in f32 and in bf16 on seeded inputs and weights.  Each run
is compared with the plain f32 reference chain at full precision; the
error bound is relative to ``max|ref|``.

One line per (network, dtype) gives the error, the count of each DMA
step case the kernels executed, and the compile and run wall seconds,
which are host set-up, not metrics.  The last line is one JSON object
naming the device.  Any error or mismatch exits non-zero without it, and
so does a machine whose first JAX device is not a TPU: there is no CPU
fallback.

Run from the root of the checkout:  python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import time
from collections import Counter
from pathlib import Path

NETWORKS = ("resnet8", "lenet5")
DTYPES = ("float32", "bfloat16")
# max|out - ref| / max|ref|: f32 runs its dots at full precision; bf16
# rounds every layer's operands and outputs to 8 mantissa bits.
TOLERANCE = {"float32": 1e-5, "bfloat16": 2e-2}
SEED = 0
# The smoke must drive all three fetch paths of the planned kernel.
ALL_CASES_NETWORK = "resnet8"

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "misses"}


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _step_cases(plan) -> Counter:
    """How many grid steps of each fetch case the plan's kernels run."""
    from repro.kernels.conv2d_offload import grid_sequence, step_case
    from repro.kernels.emit import emit_layer_kernel
    counts: Counter = Counter()
    for lp in plan.layers:
        e, s = emit_layer_kernel(lp), lp.spec
        tiles = e.grid_meta.w_out_tiles
        counts.update(
            step_case(i, jt, t_run=e.t_run, s_h=s.s_h, s_w=s.s_w,
                      h_k=s.h_k, w_k=s.w_k, w_out_tiles=tiles,
                      order=e.order)
            for i, jt in grid_sequence(e.grid_meta.h_out, tiles))
    return counts


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"needs a TPU, but JAX's first device is on platform "
              f"{dev.platform!r} ({dev.device_kind})")

    os.environ.pop("REPRO_PLAN_CACHE", None)     # plan from configs only
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.kerncheck import check_network, network_budget
    from repro.compile_cache import enable_compile_cache
    from repro.configs.networks import NETWORKS as REGISTRY
    from repro.kernels.conv2d_offload import CASE_COL, CASE_FULL, CASE_ROW
    from repro.kernels.emit import (execute_network, plan_emitable_network,
                                    reference_network)

    cache_dir = enable_compile_cache()
    cache = Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache.update(
            [_CACHE_EVENTS[event]] if event in _CACHE_EVENTS else []))
    print(f"compile cache: {cache_dir}")

    for name in NETWORKS:
        specs = list(REGISTRY[name])
        hw = network_budget(specs)
        plan = plan_emitable_network(specs, hw, name=name, verify=True)
        report = check_network(name, specs, hw=hw)
        if not report.ok:
            _fail(f"{name}: kerncheck refuses the emitted kernels\n"
                  f"{report.render()}")
        cases = _step_cases(plan)
        if name == ALL_CASES_NETWORK and \
                not all(cases[c] for c in (CASE_FULL, CASE_ROW, CASE_COL)):
            _fail(f"{name}: the plan does not run every fetch case "
                  f"({dict(cases)})")

        rng = np.random.default_rng(SEED)
        first = specs[0]
        x32 = rng.standard_normal(
            (first.c_in, first.h_in, first.w_in)).astype(np.float32)
        w32 = [(rng.standard_normal((s.c_out, s.c_in, s.h_k, s.w_k))
                / np.sqrt(s.c_in * s.h_k * s.w_k)).astype(np.float32)
               for s in specs]
        run = jax.jit(lambda x, ws, plan=plan: execute_network(
            plan, x, ws, interpret=False))
        reference = jax.jit(lambda x, ws, specs=specs: reference_network(
            specs, x, ws))

        for dtype in DTYPES:
            x = jnp.asarray(x32, dtype)
            ws = [jnp.asarray(w, dtype) for w in w32]
            before = Counter(cache)
            t0 = time.perf_counter()
            compiled = run.lower(x, ws).compile()
            compile_s = time.perf_counter() - t0
            delta = cache - before
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(x, ws))
            run_s = time.perf_counter() - t0
            # the reference sees the same (rounded) input values
            ref = reference(x.astype(jnp.float32),
                            [w.astype(jnp.float32) for w in ws])
            out = np.asarray(out.astype(jnp.float32))
            ref = np.asarray(ref)
            if out.shape != ref.shape or not np.isfinite(out).all():
                _fail(f"{name} {dtype}: output {out.shape} (finite: "
                      f"{bool(np.isfinite(out).all())}) vs reference "
                      f"{ref.shape}")
            abs_err = float(np.max(np.abs(out - ref)))
            rel_err = abs_err / float(np.max(np.abs(ref)))
            tol = TOLERANCE[dtype]
            print(f"{name} {dtype}: out {out.shape} max_abs_err={abs_err!r} "
                  f"max_rel_err={rel_err!r} tol={tol} "
                  f"steps {CASE_FULL}={cases[CASE_FULL]} "
                  f"{CASE_ROW}={cases[CASE_ROW]} "
                  f"{CASE_COL}={cases[CASE_COL]} | host set-up, not "
                  f"metrics: compile_s={compile_s!r} run_s={run_s!r} "
                  f"cache_hits={delta['hits']} "
                  f"cache_misses={delta['misses']}", flush=True)
            if not rel_err <= tol:
                _fail(f"{name} {dtype}: max_rel_err {rel_err!r} exceeds "
                      f"{tol}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
