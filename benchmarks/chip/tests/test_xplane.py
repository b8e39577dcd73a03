"""The trace reduction on a trace recorded on a TPU v5e: four requests
of ``lenet5-f32.sync`` (``record_trace.py``), two conv kernels each."""
from pathlib import Path

import pytest

import spans
import xplane
from runners.closed_loop import SPANS

TRACE = str(Path(__file__).parent / "data" / "lenet5-f32.sync.xplane.pb")


@pytest.fixture(scope="module")
def events():
    return xplane.events(TRACE)


@pytest.fixture(scope="module")
def reduced(events):
    return xplane.reduce(events, n_layers=2)


def test_op_names_keep_the_instruction_name():
    assert xplane.op_name(
        "%conv2d_offload_planned.2 = f32[28,2,14,6]{3,2,1,0} custom-call("
        "f32[32,32,128] %pad.8)") == "conv2d_offload_planned.2"
    assert xplane.op_name("jit__execute(406)") == "jit__execute(406)"


def test_every_request_and_layer_is_found(reduced):
    assert reduced["images"] == 4
    assert len(reduced["layer_s"]) == 2
    assert sum(reduced["layer_s"]) == pytest.approx(reduced["conv_s"])
    # C1 (28x28 outputs, 56 steps) outweighs C3 (10x10, 10 steps)
    assert reduced["layer_s"][0] > 3 * reduced["layer_s"][1]
    kernels = {n for n, _ in reduced["device_ops"]
               if n.startswith(xplane.CONV_OP_PREFIX)}
    assert len(kernels) == 2


def test_layers_follow_the_order_inside_each_execution(events):
    devices, host = events
    (lines,) = devices.values()
    modules = sorted((s, e) for n, s, e in lines["XLA Modules"]
                     if xplane.NETWORK_MODULE in n)
    convs = sorted((s, n) for n, s, _ in lines["XLA Ops"]
                   if n.startswith(xplane.CONV_OP_PREFIX))
    for m0, m1 in modules:
        inside = [n for s, n in convs if m0 <= s < m1]
        assert inside == ["conv2d_offload_planned.2",
                          "conv2d_offload_planned.3"]


def test_device_clock_is_aligned_to_the_launches(events):
    devices, host = events
    (lines,) = devices.values()
    lo, hi = xplane.window(host)
    launches = sorted(e for n, s, e in host
                      if n == xplane.LAUNCH and lo <= s < hi)
    starts = sorted(s for n, s, _ in lines["XLA Modules"]
                    if xplane.NETWORK_MODULE in n)
    assert len(launches) == len(starts) == 4
    # recorded raw, the device's programs start before the host launched
    # them; shifted, none does, and one starts as its launch returns
    assert starts[0] < launches[0]
    shift = xplane.clock_shift(lines["XLA Modules"], launches)
    gaps = [s - shift - e for s, e in zip(starts, launches)]
    assert min(gaps) == 0 and all(g >= 0 for g in gaps)


def test_busy_idle_and_gaps_add_up(events, reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    assert reduced["conv_s"] <= reduced["busy_s"]
    gaps = spans.reduce(events, program_spans=(),
                        span_names=SPANS)["idle_by_span"]
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    assert set(gaps) == set(SPANS) | {"other"}
    assert gaps["request.wait"] > 0
    assert len(reduced["device_ops"]) <= xplane.TOP


def test_union_and_overlap():
    merged = xplane._union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    ends = [e for _, e in merged]
    assert xplane._overlap(2, 6, merged, ends) == 2
    assert xplane._overlap(3, 5, merged, ends) == 0
