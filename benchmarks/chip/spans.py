"""Host spans of a traced window, and the device's idle time by the
innermost span that covered it.

The program writes its own host spans into the trace
(``repro.kernels.emit.SPANS``: ``executor.emit`` around re-emitting the
layers' kernels, ``executor.launch`` around the jitted call), nested
inside the runner's ``request.dispatch`` on the caller's thread.
``reduce`` reads them on the host's clock, onto which it shifts the
device's events as ``xplane.reduce`` does, and attributes each idle
stretch of the device to the innermost span that covered it.
"""
from __future__ import annotations

import xplane


def _subtract(spans, taken):
    """Sorted, disjoint intervals ``spans`` less sorted, disjoint
    ``taken``."""
    out = []
    j = 0
    for s, e in spans:
        while j < len(taken) and taken[j][1] <= s:
            j += 1
        k = j
        while k < len(taken) and taken[k][0] < e:
            if taken[k][0] > s:
                out.append((s, taken[k][0]))
            s = max(s, taken[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def _idle_gaps(lines, launches, lo, hi):
    """The chip's idle intervals inside [lo, hi), on the host's clock."""
    shift = xplane.clock_shift(lines.get("XLA Modules", ()), launches)
    busy = xplane._union(xplane._clip(
        [(s - shift, e - shift) for _, s, e in lines.get("XLA Ops", ())],
        lo, hi))
    gaps = []
    cursor = lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def reduce(trace, *, program_spans, span_names=()) -> dict:
    """Time inside each named span, and where the device sat idle, from
    ``xplane.events``' ``trace``.

    ``spans`` maps each of ``program_spans`` and ``span_names`` to the
    ``count`` of its spans inside the ``harness.window`` span and their
    summed seconds ``s``.  ``idle_by_span`` gives the device's idle
    seconds, averaged over the chips traced, by the innermost span that
    covered them: a program span before a runner span (among
    ``span_names``), then ``other``.  Its values sum to ``xplane.reduce``'s
    ``window_s - busy_s``."""
    devices, host = trace
    lo, hi = xplane.window(host)
    names = tuple(program_spans) + tuple(span_names)
    spans = {name: {"count": 0, "s": 0.0} for name in names}
    for n, s, e in host:
        if n in spans and lo <= s and e <= hi:
            spans[n]["count"] += 1
            spans[n]["s"] += (e - s) * 1e-9
    # each name keeps what no span of a name before it covers
    owned: dict = {}
    taken: list = []
    for name in names:
        own = _subtract(xplane._union((s, e) for n, s, e in host
                                      if n == name), taken)
        owned[name] = (own, [e for _, e in own])
        taken = xplane._union([*map(tuple, taken), *own])
    launches = sorted(e for n, s, e in host
                      if n == xplane.LAUNCH and lo <= s < hi)
    idle_ns = dict.fromkeys(names + ("other",), 0.0)
    for lines in devices.values():
        for g0, g1 in _idle_gaps(lines, launches, lo, hi):
            left = g1 - g0
            for name, (own, ends) in owned.items():
                part = xplane._overlap(g0, g1, own, ends)
                idle_ns[name] += part
                left -= part
            idle_ns["other"] += max(left, 0.0)
    chips = max(len(devices), 1)
    return {"window_s": (hi - lo) * 1e-9, "spans": spans,
            "idle_by_span": {n: ns * 1e-9 / chips
                             for n, ns in idle_ns.items()}}


def mean_us(spans: dict | None, name: str) -> float | None:
    """Mean microseconds of one span named ``name``, from ``reduce``'s
    ``spans``; None where there is no such span (or no trace)."""
    span = (spans or {}).get(name)
    if not span or not span["count"]:
        return None
    return span["s"] / span["count"] * 1e6
