import os
# 512 placeholder devices for the production meshes; LICM disabled because
# XLA:CPU legalizes bf16 dots by f32-upcasting operands and then hoists the
# loop-invariant converts OUT of the layer scans — materialising f32 copies
# of entire weight/cache stacks (observed +13 GB/device on decode cells).
# TPU executes bf16 dots natively, so those converts do not exist there;
# disabling the hoist makes the memory analysis reflect the target.
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion")

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and extract the roofline raw terms.

The two lines above MUST stay first: jax locks the device count on first
init, and only the dry-run wants 512 placeholder devices (smoke tests and
benches see 1 device).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k [--multi-pod] [--out benchmarks/results/dryrun]

Per cell this produces a JSON with:
  * memory_analysis (bytes/device: args, outputs, temps, generated code)
  * cost_analysis flops + bytes accessed (per-device SPMD program)
  * per-collective byte totals parsed from the optimized HLO
which EXPERIMENTS.md §Dry-run / §Roofline consume.
"""
import argparse
import json
import re
import sys
import time

import jax

from repro.launch import steps
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.models.common import SHAPES, Axes, cell_applicable

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """'bf16[4,1024]' -> bytes.  Tuple shapes handled by summing parts."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in the optimized HLO
    (per-device program -> per-device bytes moved)."""
    out = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.lstrip()
        # result-defining lines look like: '%name = TYPE op-name(' or
        # 'name.N = TYPE fusion(' — find ' = <shape> <op>(' patterns.
        for coll in _COLLECTIVES:
            if f" {coll}(" not in s and f" {coll}-start(" not in s and \
                    f" {coll}-done(" not in s:
                continue
            if f"{coll}-done(" in s:
                continue                      # counted at -start
            eq = s.find(" = ")
            if eq < 0:
                continue
            rhs = s[eq + 3:]
            op_pos = rhs.find(coll)
            shape_str = rhs[:op_pos]
            out[coll] += _shape_bytes(shape_str)
            out["count"] += 1
            break
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def lower_cell(arch: str, shape: str, multi_pod: bool):
    api = registry.get(arch)
    cell = SHAPES[shape]
    ok, why = cell_applicable(api.cfg, cell)
    if not ok:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = Axes.for_mesh(mesh)
    with jax.set_mesh(mesh):
        t0 = time.time()
        if cell.kind == "train":
            jitted = steps.jit_train_step(api, axes, cell)
            args = steps.abstract_train_args(api, cell, axes)
        elif cell.kind == "prefill":
            jitted = steps.jit_prefill_step(api, axes, cell)
            args = steps.abstract_serve_args(api, cell, axes)
        else:
            jitted = steps.jit_decode_step(api, axes, cell)
            args = steps.abstract_serve_args(api, cell, axes)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    from repro.launch import hlo_stats
    cost = hlo_stats.cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    colls = collective_bytes(hlo)          # raw text scan (bodies once)
    stats = hlo_stats.analyze(hlo)         # trip-count-corrected roll-up

    result = {
        "arch": arch, "shape": shape,
        "mesh": "pod2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "multi_pod": multi_pod,
        "status": "ok",
        "kind": cell.kind,
        "seq_len": cell.seq_len,
        "global_batch": cell.global_batch,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_device_bytes": (mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes
                                  - mem.alias_size_in_bytes),
        },
        "cost": {
            # raw XLA numbers: while bodies counted ONCE (undercount for
            # scanned models) — kept for reference/debugging.
            "flops_per_device_raw": cost.get("flops", 0.0),
            "bytes_accessed_per_device_raw": cost.get("bytes accessed", 0.0),
        },
        # trip-count-corrected structural analysis (launch/hlo_stats.py):
        # the numbers §Roofline uses.
        "analyzed": {
            "matmul_flops_per_device": stats.flops,
            "bytes_accessed_per_device": stats.bytes_accessed,
            "collective_bytes_per_device": stats.collective_bytes,
            "collective_bytes_total": stats.collective_total,
            "collective_count": stats.collective_count,
            "unknown_trip_loops": stats.unknown_trip_loops,
        },
        "collectives_per_device_bytes_raw": colls,
        "hlo_bytes": len(hlo),
    }
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--shape", required=True, choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    args = ap.parse_args(argv)

    result = lower_cell(args.arch, args.shape, args.multi_pod)
    mesh_tag = "pod" if args.multi_pod else "single"
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(
        args.out, f"{args.arch}_{args.shape}_{mesh_tag}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("memory", "cost")}, indent=1))
    if result["status"] == "ok":
        print("memory_analysis:", json.dumps(result["memory"]))
        print("cost_analysis:", json.dumps(result["cost"]))
    print("saved ->", path)
    return 0 if result["status"] in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
