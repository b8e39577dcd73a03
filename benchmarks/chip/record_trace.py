"""Record a small profiler trace of one cell on the chip, and describe it.

    python3 benchmarks/chip/record_trace.py --workload lenet5-f32.sync \\
        --requests 4 --out benchmarks/chip/tests/data/lenet5-f32.sync.xplane.pb

Sets the cell up as a run does (seed 0), traces ``--requests`` requests
inside a ``harness.window`` span, copies the ``.xplane.pb`` to ``--out``
and prints every plane and line of it with its first events, which is
how the conv kernels' names in ``xplane.py`` were found.  The trace
test (``tests/test_xplane.py``) reads the recorded file.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import tempfile

import run


def describe(path: str, first: int) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for e in events[:first]:
                print(f"    {e.name!r} start_ns={e.start_ns} "
                      f"duration_ns={e.duration_ns}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--out", required=True)
    p.add_argument("--first", type=int, default=40,
                   help="events to print per line")
    args = p.parse_args(argv)
    try:
        _, cell, cfg, traffic = run.load_cell(args.workload)
        run.open_device(cell)
    except run.BenchError as e:
        run.log(f"record_trace: {e}")
        return run.EXIT_REFUSED
    s = run.set_up(cfg, traffic, 0, run.log)
    log_dir = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        result, path = run.trace_window(
            s["runner"], s["call"], s["images"], traffic, log_dir,
            requests=args.requests)
        shutil.copyfile(path, args.out)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    print(f"traced {result}; trace written to {args.out}")
    describe(args.out, args.first)
    return 0


if __name__ == "__main__":
    sys.exit(main())
