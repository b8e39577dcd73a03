"""The configuration files hold the registry's networks at the budget
the smoke run plans under, and plan as the benchmark's cells say."""
import json
from collections import Counter

import pytest

import run

PAIRS = [("resnet8-f32", "resnet8"), ("lenet5-f32", "lenet5")]


def _config(name):
    return json.loads((run.HERE / "configs" / f"{name}.json").read_text())


def _specs(cfg):
    from repro.core.conv_spec import ConvSpec
    return [ConvSpec(**layer) for layer in cfg["layers"]]


@pytest.mark.parametrize("name, registered", PAIRS)
def test_config_reproduces_the_registry(name, registered):
    from repro.analysis.kerncheck import network_budget
    from repro.configs.networks import NETWORKS
    cfg = _config(name)
    specs = _specs(cfg)
    assert specs == list(NETWORKS[registered])
    hw = network_budget(specs)
    assert (cfg["budget"]["size_mem"], cfg["budget"]["nbop_pe"]) == \
        (hw.size_mem, hw.nbop_pe)


@pytest.mark.parametrize("name, t_runs, cases", [
    ("resnet8-f32", [16, 16, 16, 16, 16, 8, 8],
     {"full": 7, "row-delta": 137, "col-delta": 96}),
    ("lenet5-f32", [14, 10], {"full": 2, "row-delta": 36, "col-delta": 28}),
])
def test_plan_at_the_configs_budget(name, t_runs, cases):
    """The plan the harness makes, by its ``ConvSpec`` route."""
    from repro.kernels import emit
    from repro.kernels.conv2d_offload import grid_sequence, step_case
    from repro.kernels.emit import emit_layer_kernel
    plan = run.plan_network(_config(name), emit)
    emitted = [emit_layer_kernel(lp) for lp in plan.layers]
    assert [e.t_run for e in emitted] == t_runs
    seen = Counter()
    for e in emitted:
        s, tiles = e.spec, e.grid_meta.w_out_tiles
        seen.update(step_case(i, jt, t_run=e.t_run, s_h=s.s_h, s_w=s.s_w,
                              h_k=s.h_k, w_k=s.w_k, w_out_tiles=tiles,
                              order=e.order)
                    for i, jt in grid_sequence(e.grid_meta.h_out, tiles))
    assert dict(seen) == cases
