"""Closed-loop client: one caller with up to ``in_flight`` requests out.

Request k sends image ``k mod len(images)``.  The client dispatches
back to back; once ``in_flight`` requests are outstanding it waits for
the oldest before dispatching the next.  With ``in_flight`` 1 every
request is dispatched and then waited for, as a real-time loop that
must finish each frame before the next one does.

Host spans ``harness.next_image``, ``request.dispatch`` and
``request.wait`` go into the profiler's trace when one is recording;
they cost a few hundred nanoseconds when none is.
"""
from __future__ import annotations

import collections
import time
import traceback
from typing import Callable, Sequence

import jax

SPANS = ("harness.next_image", "request.dispatch", "request.wait")


def run(call: Callable, images: Sequence, traffic: dict, *,
        seconds: float | None = None, requests: int | None = None,
        on_output: Callable | None = None) -> dict:
    """Drive ``call`` for ``seconds`` (or for ``requests`` requests),
    with ``traffic["in_flight"]`` requests out at most.

    Returns counts and host times: ``attempted`` requests dispatched,
    ``completed`` those whose wait returned before the loop closed,
    ``failed`` those that raised, ``window_s`` from the first dispatch
    to the loop's close, and ``dispatch_s`` the summed host time inside
    ``call``.  ``on_output(k, out)`` sees every request's output as it
    is dispatched.  Requests still out at the close are waited for
    afterwards and are not counted as completed."""
    if (seconds is None) == (requests is None):
        raise ValueError("give exactly one of seconds and requests")
    in_flight = int(traffic["in_flight"])
    if in_flight < 1:
        raise ValueError(f"in_flight must be at least 1, not {in_flight}")
    annotate = jax.profiler.TraceAnnotation
    clock = time.perf_counter
    n_images = len(images)
    pending: collections.deque = collections.deque()
    attempted = completed = failed = 0
    dispatch_s = 0.0

    def wait_oldest() -> None:
        nonlocal completed, failed
        out = pending.popleft()
        try:
            with annotate("request.wait"):
                out.block_until_ready()
            completed += 1
        except Exception:   # a request that fails is counted, not fatal
            failed += 1
            traceback.print_exc()

    start = clock()
    deadline = None if seconds is None else start + seconds
    while True:
        if deadline is not None:
            if clock() >= deadline:
                break
        elif attempted >= requests:
            break
        with annotate("harness.next_image"):
            image = images[attempted % n_images]
        t0 = clock()
        try:
            with annotate("request.dispatch"):
                out = call(image)
        except Exception:   # a request that fails is counted, not fatal
            failed += 1
            traceback.print_exc()
            out = None
        dispatch_s += clock() - t0
        if out is not None:
            if on_output is not None:
                on_output(attempted, out)
            pending.append(out)
        attempted += 1
        while len(pending) >= in_flight:
            wait_oldest()
    window_s = clock() - start
    done_in_window = completed
    while pending:
        wait_oldest()
    return {"attempted": attempted, "completed": done_in_window,
            "failed": failed, "window_s": window_s,
            "dispatch_s": dispatch_s}
