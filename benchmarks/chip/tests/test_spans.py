"""The span reduction on traces recorded on a TPU v5e: four requests of
``lenet5-f32.sync`` each (``record_trace.py``), one recorded before the
executor wrote spans of its own and one after."""
from pathlib import Path

import pytest

import spans
import xplane
from repro.kernels.emit import SPANS
from runners.closed_loop import SPANS as RUNNER

DATA = Path(__file__).parent / "data"
TRACES = [str(DATA / "lenet5-f32.sync.xplane.pb"),
          str(DATA / "lenet5-f32.sync.spans.xplane.pb")]
NEW = TRACES[1]


def _reduce(path, program_spans=SPANS):
    return spans.reduce(xplane.events(path), program_spans=program_spans,
                        span_names=RUNNER)


def _host(path, name):
    _, host = xplane.events(path)
    return sorted((s, e) for n, s, e in host if n == name)


def test_every_request_has_one_span_of_each_name():
    r = _reduce(NEW)
    assert {n: c["count"] for n, c in r["spans"].items()} == \
        dict.fromkeys(SPANS + RUNNER, 4)
    emit, launch = (r["spans"][n]["s"] for n in SPANS)
    assert 0 < emit + launch <= r["spans"]["request.dispatch"]["s"]


def test_program_spans_nest_in_one_dispatch_each():
    dispatches = _host(NEW, "request.dispatch")
    emits, launches = (_host(NEW, n) for n in SPANS)
    holders = []
    for (e0, e1), (l0, l1) in zip(emits, launches):
        assert e1 <= l0     # emission ends before the launch starts
        around = [d for d in dispatches if d[0] <= e0 and l1 <= d[1]]
        assert len(around) == 1
        holders.append(around[0])
    assert len(set(holders)) == len(emits) == 4


@pytest.mark.parametrize("path", TRACES)
def test_idle_by_span_sums_to_the_idle_window(path):
    r = _reduce(path)
    base = xplane.reduce(xplane.events(path), n_layers=2)
    assert r["window_s"] == base["window_s"]
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-9)


@pytest.mark.parametrize("path", TRACES)
def test_runner_idle_is_idle_gaps_less_the_program_spans(path):
    r = _reduce(path)
    gaps = _reduce(path, program_spans=())["idle_by_span"]
    idle = r["idle_by_span"]
    program = sum(idle[n] for n in SPANS)
    assert idle["request.dispatch"] + program == pytest.approx(
        gaps.get("request.dispatch", 0.0), rel=1e-9, abs=1e-12)
    for name in ("request.wait", "harness.next_image", "other"):
        assert idle[name] == pytest.approx(gaps.get(name, 0.0),
                                           rel=1e-9, abs=1e-12)


def test_a_trace_without_program_spans_reads_none():
    r = _reduce(TRACES[0])
    assert all(r["spans"][n] == {"count": 0, "s": 0.0} for n in SPANS)
    assert all(r["idle_by_span"][n] == 0 for n in SPANS)


def test_mean_us():
    r = _reduce(NEW)
    for name in SPANS:
        span = r["spans"][name]
        assert spans.mean_us(r["spans"], name) == pytest.approx(
            span["s"] / 4 * 1e6)
    assert spans.mean_us(_reduce(TRACES[0])["spans"], SPANS[0]) is None
    assert spans.mean_us(None, SPANS[0]) is None


def test_subtract():
    assert spans._subtract([(0, 10), (12, 14)], [(2, 3), (5, 13)]) == \
        [(0, 2), (3, 5), (13, 14)]
    assert spans._subtract([(0, 4)], []) == [(0, 4)]
    assert spans._subtract([(0, 4)], [(0, 4)]) == []
