"""Readings that set the limit of ``max_rel_err``, on the chip.

    python3 benchmarks/chip/control.py --workload resnet8-f32.stream \\
        --seconds 1 --seeds 101 102 103 ...

For each seed, in one process: the weights and images are made from the
seed, a window of ``--seconds`` drives the program at the cell's own
load as a run does, and the run's sample of outputs is compared with the
reference.  That is the program's reading.  The control is the
reference in three bf16 passes (what ``Precision.HIGH`` does), the
nearest precision below the configuration's float32 at ``highest``,
put in the program's place on the same sampled images.  Prints one JSON
line per seed and then the largest program reading and the smallest
control reading.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    try:
        _, cell, cfg, traffic = run.load_cell(args.workload)
        run.open_device(cell)
    except run.BenchError as e:
        run.log(f"control: {e}")
        return run.EXIT_REFUSED
    import jax.numpy as jnp
    from repro.kernels import emit

    s = run.set_up(cfg, traffic, args.seeds[0], run.log)
    plan, runner = s["plan"], s["runner"]
    reference = run.load_module("references", cfg["reference"])
    exact = reference.make_forward(cfg)
    control = reference.make_forward(cfg, passes=3)
    rows = []
    for seed in args.seeds:
        weights, images = run.make_inputs(cfg, int(traffic["pool_images"]),
                                          seed)
        sample = run.Sample(run.CHECK_SAMPLE, seed)
        window = runner.run(
            lambda x, w=weights: emit.execute_network(plan, x, w),
            images, traffic, seconds=args.seconds, on_output=sample.add)
        items = sorted(sample.items, key=lambda item: item[0])
        xs = jnp.stack([images[k % len(images)] for k, _ in items])
        outs = np.stack([np.asarray(o, np.float32) for _, o in items])
        refs = np.asarray(exact(xs, weights), np.float32)
        ctrl = np.asarray(control(xs, weights), np.float32)
        row = {"seed": seed, "completed": window["completed"],
               "compared": len(items),
               "program": run.max_rel_err(outs, refs),
               "control": run.max_rel_err(ctrl, refs)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": max(r["program"] for r in rows),
        "control_min": min(r["control"] for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
