"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Modules`` line has one event per executed program and whose
``XLA Ops`` line has one event per operation, and a host plane whose
thread lines carry the harness's ``TraceAnnotation`` spans and the
runtime's ``PJRT_LoadedExecutable_Execute`` launch of each program.

The device's clock is not the host's: on the v5e device events read up
to about a millisecond early against the host (a program "starting"
before the host launched it).  The window holds nothing but the
network's executions, so the k-th network program on the device is the
k-th launch on the host, and the device's events are shifted so that the
earliest-starting program starts as its launch returns.  A program can
start a little before its launch returns, so the device's events may
read up to that much (tens of microseconds) late after the shift.

An operation's event is named by its HLO instruction's text
(``%conv2d_offload_planned.9 = f32[32,2,16,16]... custom-call(...)``);
``op_name`` keeps the instruction's name.  XLA names each call of
``conv2d_offload_planned`` after that function, then ``.<n>``.  The
kernels carry no layer index, so within each execution of the network
program (a module named ``jit__execute(<id>)``) the k-th conv kernel is
layer k.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os

CONV_OP_PREFIX = "conv2d_offload_planned"
NETWORK_MODULE = "_execute"
WINDOW_SPAN = "harness.window"
LAUNCH = "PJRT_LoadedExecutable_Execute"
TOP = 10


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """``%name = <shape> op(...)`` -> ``name``; other names unchanged."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def events(path: str):
    """The trace at ``path`` as plain tuples: (device lines by chip, host
    events), read once and handed to ``reduce`` and ``spans.reduce``.

    Device lines map a chip's plane name to {line name: [(name, start_ns,
    end_ns)]}; host events are [(name, start_ns, end_ns)] over all host
    threads."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return devices, host


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(a0, a1, spans, ends) -> float:
    """Length of [a0, a1) covered by the sorted, merged ``spans``, whose
    end points are ``ends``."""
    total = 0.0
    for i in range(bisect.bisect_right(ends, a0), len(spans)):
        s, e = spans[i]
        if s >= a1:
            break
        total += min(e, a1) - max(s, a0)
    return total


def clock_shift(modules, launches) -> float:
    """Nanoseconds to subtract from a chip's times to put them on the
    host's clock: pair its network programs in order with the host's
    launches (their end times), and align the pair whose program starts
    soonest after its launch.  0 where the counts differ."""
    starts = sorted(s for n, s, _ in modules if NETWORK_MODULE in n)
    if not starts or len(starts) != len(launches):
        return 0.0
    return min(s - e for s, e in zip(starts, launches))


def window(host) -> tuple[int, int]:
    """(start, end) of the one ``harness.window`` span among ``host``."""
    windows = [(s, e) for name, s, e in host if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    return windows[0]


def reduce(trace, *, n_layers: int) -> dict:
    """The traced window's device time, and its conv kernel time per
    layer and per image, from ``events``' ``trace``.

    ``window_s`` is the length of the ``harness.window`` span, and only
    device work inside it counts.  ``busy_s`` is the union of the
    device's operation intervals, averaged over the chips traced.
    ``images`` counts the network programs that ran in the window,
    ``conv_s`` their conv kernels' summed time and ``layer_s`` that time
    by layer (None where an execution did not hold exactly
    ``n_layers`` kernels).  ``device_ops`` lists the operations that
    took most time.  Where the device sat idle is ``spans.reduce``'s."""
    devices, host = trace
    lo, hi = window(host)
    launches = sorted(e for n, s, e in host if n == LAUNCH and lo <= s < hi)
    busy_ns = 0.0
    conv_ns = 0.0
    images = 0
    layer_ns = [0.0] * n_layers
    mapped = True
    op_ns: collections.Counter = collections.Counter()
    for lines in devices.values():
        shift = clock_shift(lines.get("XLA Modules", ()), launches)
        lines = {name: [(n, s - shift, e - shift) for n, s, e in events]
                 for name, events in lines.items()}
        ops = [(n, s, e) for n, s, e in lines.get("XLA Ops", ())
               if e > lo and s < hi]
        busy = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for n, s, e in ops:
            op_ns[n] += min(e, hi) - max(s, lo)
        convs = sorted((s, e) for n, s, e in ops
                       if n.startswith(CONV_OP_PREFIX))
        modules = sorted((s, e) for n, s, e in lines.get("XLA Modules", ())
                         if NETWORK_MODULE in n and s >= lo and e <= hi)
        images += len(modules)
        i = 0
        for m0, m1 in modules:
            while i < len(convs) and convs[i][0] < m0:
                i += 1
            inside = []
            while i < len(convs) and convs[i][0] < m1:
                inside.append(convs[i][1] - convs[i][0])
                i += 1
            conv_ns += sum(inside)
            if len(inside) == n_layers:
                for k, d in enumerate(inside):
                    layer_ns[k] += d
            else:
                mapped = False
    chips = max(len(devices), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / chips,
        "images": images,
        "conv_s": conv_ns * 1e-9,
        "layer_s": ([ns * 1e-9 for ns in layer_ns]
                    if mapped and images else None),
        "device_ops": [[n, ns * 1e-9] for n, ns in op_ns.most_common(TOP)],
    }
