"""``plan_layers`` plans a network given as layer dicts, graph keys and
all, and ``execute_network`` runs it on the CPU (interpret mode): a
small ResNet-style graph against a plain ``jax.numpy`` forward written
here, the chains of shape keys alone as ``plan_emitable_network`` plans
and runs them, the refusals, and what the executor counts."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.analysis.kerncheck import network_budget
from repro.analysis.verifier import verify_network_plan
from repro.configs.networks import NETWORKS
from repro.core.conv_spec import ConvSpec
from repro.core.cost_model import HardwareModel
from repro.core.network_planner import plan_network
from repro.kernels.emit import (
    SHAPE_KEYS, GraphError, emit_layer_kernel, execute_network,
    plan_emitable_network, plan_layers)
from repro.obs.metrics import REGISTRY

REPO = Path(__file__).resolve().parents[1]
RESNET50 = REPO / "benchmarks" / "chip" / "configs" / "resnet50-f32.json"


def _conv(c_in, h_in, n, k, s, **graph):
    return dict(c_in=c_in, h_in=h_in, w_in=h_in, n_kernels=n, h_k=k, w_k=k,
                s_h=s, s_w=s, **graph)


# A ResNet in small: a 7x7/2 stem on 3 channels, the 3x3/2 max-pool, a
# bottleneck opened by a 1x1/2 projection with the stride on its 3x3, an
# identity bottleneck, the global average pool and a 1x1 FC.  136
# channels take two lane tiles a pixel.
SMALL = [
    _conv(3, 38, 8, 7, 2, input=-1, relu=True),                  # 0: 16x16
    _conv(8, 8, 136, 1, 2, input=0, pool="max3x3s2p1"),          # 1: 4x4
    _conv(8, 8, 4, 1, 1, input=0, pool="max3x3s2p1", relu=True),  # 2: 8x8
    _conv(4, 10, 4, 3, 2, input=2, pad=1, relu=True),            # 3: 4x4
    _conv(4, 4, 136, 1, 1, input=3, add=1, relu=True),           # 4
    _conv(136, 4, 4, 1, 1, input=4, relu=True),                  # 5
    _conv(4, 6, 4, 3, 1, input=5, pad=1, relu=True),             # 6
    _conv(4, 4, 136, 1, 1, input=6, add=4, relu=True),           # 7
    _conv(136, 1, 10, 1, 1, input=7, pool="avg_global"),         # 8
]


def _weights(layers, seed):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(
        (la["n_kernels"], la["c_in"], la["h_k"], la["w_k"]))
        / np.sqrt(la["c_in"] * la["h_k"] * la["w_k"]), jnp.float32)
        for la in layers]


def _plain_forward(x, weights):
    """SMALL's forward pass, (C, H, W), written out layer by layer."""
    def conv(h, w, s, pad=0):
        return lax.conv_general_dilated(
            h[None], w, (s, s), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            precision=lax.Precision.HIGHEST)[0]

    relu = jax.nn.relu
    stem = relu(conv(x, weights[0], 2))
    pooled = lax.reduce_window(stem, -jnp.inf, lax.max, (1, 3, 3),
                               (1, 2, 2), ((0, 0), (1, 1), (1, 1)))
    short = conv(pooled, weights[1], 2)
    h = relu(conv(pooled, weights[2], 1))
    h = relu(conv(h, weights[3], 2, pad=1))
    block1 = relu(conv(h, weights[4], 1) + short)
    h = relu(conv(block1, weights[5], 1))
    h = relu(conv(h, weights[6], 1, pad=1))
    block2 = relu(conv(h, weights[7], 1) + block1)
    return conv(block2.mean(axis=(1, 2), keepdims=True), weights[8], 1)


def _spec(layer):
    return ConvSpec(**{k: layer[k] for k in SHAPE_KEYS})


def _hw(layers):
    return network_budget([_spec(la) for la in layers])


def test_small_resnet_matches_a_plain_forward():
    plan = plan_layers(SMALL, _hw(SMALL), name="small-resnet")
    assert len(plan.layers) == len(SMALL)
    assert all(lp.gross_duration > 0 for lp in plan.layers)
    x = jnp.asarray(np.random.default_rng(5).standard_normal((3, 38, 38)),
                    jnp.float32)
    ws = _weights(SMALL, 6)
    out = execute_network(plan, x, ws)
    exp = _plain_forward(x, ws)
    assert out.shape == exp.shape == (10, 1, 1)
    np.testing.assert_allclose(out, exp, rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(exp))))


@pytest.mark.parametrize("name, t_runs", [
    ("resnet8", [16, 16, 16, 16, 16, 8, 8]),
    ("lenet5", [14, 10]),
])
def test_shape_keys_alone_plan_and_run_as_the_chain(name, t_runs):
    specs = list(NETWORKS[name])
    hw = network_budget(specs)
    chain = plan_emitable_network(specs, hw, name=name)
    listed = plan_layers([dataclasses.asdict(s) for s in specs], hw,
                         name=name)
    ours = [emit_layer_kernel(lp) for lp in listed.layers]
    theirs = [emit_layer_kernel(lp) for lp in chain.layers]
    assert [e.t_run for e in ours] == [e.t_run for e in theirs] == t_runs
    assert [e.grid_meta for e in ours] == [e.grid_meta for e in theirs]
    assert [lp.strategy for lp in listed.layers] == \
        [lp.strategy for lp in chain.layers]
    assert [lp.gross_duration for lp in listed.layers] == \
        [lp.gross_duration for lp in chain.layers]
    rng = np.random.default_rng(7)
    s0 = specs[0]
    x = jnp.asarray(rng.standard_normal((s0.c_in, s0.h_in, s0.w_in)),
                    jnp.float32)
    ws = [jnp.asarray(rng.standard_normal((s.c_out, s.c_in, s.h_k, s.w_k)),
                      jnp.float32) for s in specs]
    a = np.asarray(execute_network(listed, x, ws))
    b = np.asarray(execute_network(chain, x, ws))
    assert a.shape == b.shape and a.tobytes() == b.tobytes()


def _edited(k, **changes):
    layers = [dict(la) for la in SMALL]
    layers[k].update(changes)
    return layers


@pytest.mark.parametrize("layers, match", [
    (_edited(4, add=5), "not an earlier layer"),            # a later join
    (_edited(3, input=3), "not an earlier layer"),          # reads itself
    (_edited(4, add=2), "adds layer 2"),                    # 8x8 onto 4x4
    (_edited(5, c_in=128), "channels"),                     # channel mismatch
    (_edited(3, pad=2), "is not its"),                      # map mismatch
    (_edited(2, pool="max4x4"), "unknown pool"),
    (_edited(6, dilation=2), "unknown keys"),
    (_edited(0, pad=3), "layer 0"),
])
def test_plan_layers_refuses_a_graph_it_cannot_run(layers, match):
    with pytest.raises(GraphError, match=match) as info:
        plan_layers(layers, _hw(SMALL), name="bad")
    assert isinstance(info.value, ValueError)


def test_a_graph_with_joins_plans_without_reuse():
    specs = [_spec(la) for la in SMALL]
    plan = plan_layers(SMALL, _hw(SMALL), name="small-resnet")
    assert not any(lp.reuse_input or lp.reuse_output or lp.window_rows
                   for lp in plan.layers)
    with pytest.raises(ValueError, match="allow_reuse=False"):
        plan_network(specs, _hw(SMALL), name="dag", graph=plan.graph)
    # the verifier holds a plan to it too
    layers = list(plan.layers)
    layers[5] = dataclasses.replace(layers[5], reuse_output=True)
    layers[6] = dataclasses.replace(layers[6], reuse_input=True)
    report = verify_network_plan(dataclasses.replace(plan,
                                                     layers=tuple(layers)))
    assert {d.rule for d in report.diagnostics} >= {"reuse/graph"}


def _counted(plan, shapes,
             keys=("executor/traces", "executor/joins", "executor/pools")):
    """What tracing ``plan``'s program counts."""
    jax.clear_caches()
    before = [REGISTRY.get(k) for k in keys]
    jax.eval_shape(lambda x, ws: execute_network(plan, x, ws), *shapes)
    return [REGISTRY.get(k) - b for k, b in zip(keys, before)]


def _shapes(specs):
    s0 = specs[0]
    return (jax.ShapeDtypeStruct((s0.c_in, s0.h_in, s0.w_in), jnp.float32),
            [jax.ShapeDtypeStruct((s.c_out, s.c_in, s.h_k, s.w_k),
                                  jnp.float32) for s in specs])


@pytest.mark.parametrize("name, joins, pools", [
    ("resnet8", 0, 2), ("lenet5", 0, 1)])
def test_the_chains_count_their_pools(name, joins, pools):
    specs = list(NETWORKS[name])
    plan = plan_emitable_network(specs, network_budget(specs), name=name)
    assert _counted(plan, _shapes(specs)) == [1, joins, pools]


def test_resnet50_counts_its_joins_and_pools_and_plans_alone():
    layers = json.loads(RESNET50.read_text())["layers"]
    budget = json.loads(RESNET50.read_text())["budget"]
    plan = plan_layers(layers, HardwareModel(**budget), name="resnet50")
    assert len(plan.layers) == 54
    assert not any(lp.reuse_input or lp.reuse_output or lp.window_rows
                   for lp in plan.layers)
    specs = [lp.spec for lp in plan.layers]
    assert _counted(plan, _shapes(specs)) == [1, 16, 2]


def _plan_of(name):
    if name in NETWORKS:
        specs = list(NETWORKS[name])
        return plan_emitable_network(specs, network_budget(specs), name=name)
    cfg = json.loads(RESNET50.read_text())
    layers = cfg["layers"][:1] if name == "resnet50-stem" else cfg["layers"]
    return plan_layers(layers, HardwareModel(**cfg["budget"]), name=name)


@pytest.mark.parametrize("name, taps, dots", [
    ("resnet8", 63, 18), ("lenet5", 50, 3), ("resnet50-stem", 49, 2),
    ("resnet50", 230, 434)])
def test_the_executor_counts_taps_and_the_dots_they_take(name, taps, dots):
    """Each trace counts the layers' kernel taps and the dots a grid step
    issues: resnet8's 3x3s on 3, 16, 32 and 64 channels take 1, 2, 3 and
    5 dots; lenet5's 5x5s on 1 and 6 channels 1 and 2; ResNet-50's 7x7
    stem 2, its three 3x3s on 64 channels 5 each, and every layer wider
    than 128 channels one dot per tap and lane tile."""
    plan = _plan_of(name)
    specs = [lp.spec for lp in plan.layers]
    assert _counted(plan, _shapes(specs), keys=(
        "executor/traces", "executor/taps", "executor/tap_dots")) == \
        [1, taps, dots]
    assert {"executor/taps", "executor/tap_dots"} <= set(REGISTRY.keys())
