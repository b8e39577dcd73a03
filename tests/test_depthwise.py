"""Channel groups through the formalism, the planned kernel and the graph:
``ConvSpec.groups`` against hand counts and its refusals, the depthwise
step body against ``lax`` (CPU, interpret mode), ``kerncheck`` on
depthwise and stride-equals-kernel layers, and a ConvNeXt in small
(LayerNorm, GELU, depthwise convs, patchify and downsampling convs)
against a plain ``jax.numpy`` forward written here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.analysis.kerncheck import (
    build_conv_trace, check_conv_trace, network_budget)
from repro.configs.clusters import make_cluster
from repro.configs.networks import NETWORKS
from repro.core.conv_spec import ConvSpec
from repro.core.cost_model import HardwareModel
from repro.core.multichip import plan_multichip_network
from repro.core.strategies import zigzag
from repro.core.strategies_s2 import best_s2, kernel_major
from repro.kernels.conv2d_offload import conv2d_offload_planned
from repro.kernels.emit import (
    GraphError, emit_layer_kernel, execute_network, kernel_vmem_elements,
    plan_emitable_network, plan_layers)
from repro.obs.events import decompose_step
from repro.obs.metrics import REGISTRY
from repro.plancache.codec import strategy_from_json, strategy_to_json
from repro.sim.layer import ConvLayer

DW = ConvSpec(c_in=1, h_in=10, w_in=10, n_kernels=8, h_k=3, w_k=3,
              groups=8)


def _conv(c_in, h_in, n, k, s, **graph):
    return dict(c_in=c_in, h_in=h_in, w_in=h_in, n_kernels=n, h_k=k, w_k=k,
                s_h=s, s_w=s, **graph)


# ---------------------------------------------------------------- spec

def test_a_depthwise_spec_counts_kernels_by_depth_and_pixels_by_input():
    assert DW.in_channels == 8
    assert DW.kernel_elements == 8 * 1 * 3 * 3          # Λ
    assert DW.nb_op_value == 3 * 3                       # MACs a value
    strat = zigzag(DW, 4)                        # 8x8 out, 2 runs a row
    # the first step loads a 3x6 window of 8-channel pixels and Λ
    first = strat.to_steps()[0]
    d = decompose_step(first, DW, HardwareModel(nbop_pe=1 << 20))
    assert d.load_elements == 3 * 6 * 8 + 8 * 9
    assert strat.peak_footprint_elements() == 72 + 3 * 6 * 8 + 2 * 4 * 8
    # Λ, then window, column delta and row delta of 8-channel pixels,
    # then two (4, 8) output blocks
    assert kernel_vmem_elements(DW, 4) == (72 + 8 * 3 * 6 + 8 * 3 * 4
                                           + 8 * 1 * 6 + 2 * 8 * 4)


def test_a_dense_spec_is_one_group():
    dense = ConvSpec(c_in=8, h_in=10, w_in=10, n_kernels=8, h_k=3, w_k=3)
    assert dense.groups == 1 and dense.in_channels == dense.c_in == 8
    assert dense.kernel_elements == 8 * 8 * 9 and dense.nb_op_value == 72


@pytest.mark.parametrize("c_in, n, groups", [
    (1, 8, 4),        # a general grouped conv
    (2, 8, 8),        # a depthwise multiplier of kernels two deep
    (1, 8, 0),
    (1, 8, 16),
])
def test_other_groups_are_refused_by_name(c_in, n, groups):
    with pytest.raises(ValueError, match=f"groups={groups}"):
        ConvSpec(c_in=c_in, h_in=10, w_in=10, n_kernels=n, h_k=3, w_k=3,
                 groups=groups)


def test_s2_multichip_and_the_simulator_refuse_groups():
    hw = HardwareModel(nbop_pe=1 << 20, size_mem=400)
    with pytest.raises(NotImplementedError, match="groups=8"):
        best_s2(DW, hw)
    with pytest.raises(NotImplementedError, match="groups=8"):
        kernel_major(DW, 4, 2)
    with pytest.raises(NotImplementedError, match="groups=8"):
        plan_multichip_network([DW], make_cluster(2, size_mem=4000))
    with pytest.raises(NotImplementedError, match="groups=8"):
        ConvLayer.random(DW)


def test_the_plan_cache_keeps_groups_and_dense_keys_unchanged():
    strat = zigzag(DW, 4)
    doc = strategy_to_json(strat)
    assert doc["spec"][-1] == 8 and len(doc["spec"]) == 9
    assert strategy_from_json(doc) == strat
    dense = zigzag(ConvSpec(3, 10, 10, 8, 3, 3), 4)
    assert len(strategy_to_json(dense)["spec"]) == 8


# ---------------------------------------------------------------- kernel

def _lax_conv(x, w, s, groups=1):
    """x (H, W, C), w (Hk, Wk, C / groups, N) -> (H_out, W_out, N)."""
    return lax.conv_general_dilated(
        x[None], w, (s, s), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=lax.Precision.HIGHEST)[0]


@pytest.mark.parametrize("c, t_run, dtype", [
    (c, t, "float32") for c in (8, 96, 130) for t in (4, 8)
] + [(96, 4, "bfloat16")])
def test_the_depthwise_step_body_matches_lax(c, t_run, dtype):
    """7x7, padding 3, stride 1 on an 8x8 map: 96 channels lane-padded to
    128, 130 to two lane tiles a pixel; bf16 in its two-row pixel,
    accumulated in f32 and rounded once to bf16 (bf16's unit roundoff,
    2**-8 of the value)."""
    rng = np.random.default_rng(c + t_run)
    x = jnp.asarray(rng.standard_normal((8, 8, c)), dtype)
    w = jnp.asarray(rng.standard_normal((7, 7, 1, c)) / 7, dtype)
    xp = jnp.pad(x, ((3, 3), (3, 3), (0, 0)))
    out = conv2d_offload_planned(xp, w, t_run=t_run, interpret=True)
    exp = _lax_conv(xp.astype(jnp.float32), w.astype(jnp.float32), 1,
                    groups=c)
    assert out.shape == exp.shape == (8, 8, c) and out.dtype == dtype
    rtol = 0 if dtype == "float32" else 2 ** -8
    np.testing.assert_allclose(out.astype(jnp.float32), exp, rtol=rtol,
                               atol=1e-5)


@pytest.mark.parametrize("layer", [
    _conv(1, 14, 8, 7, 1, groups=8),
    _conv(1, 14, 96, 7, 1, groups=96),
    _conv(1, 14, 130, 7, 1, groups=130),
    _conv(3, 32, 8, 4, 4),                  # patchify: 4x4, stride 4
    _conv(8, 8, 16, 2, 2),                  # downsampling: 2x2, stride 2
], ids=["dw8", "dw96", "dw130", "4x4s4", "2x2s2"])
def test_kerncheck_proves_depthwise_and_stride_equals_kernel_layers(layer):
    spec = ConvSpec(**layer)
    # a depthwise Λ is smaller than its window: 2 x Λ would fit no plan
    hw = HardwareModel(nbop_pe=1 << 20, size_mem=1 << 16)
    plan = plan_layers([layer], hw, name="one")
    e = emit_layer_kernel(plan.layers[0])
    assert e.vmem_elements == kernel_vmem_elements(spec, e.t_run)
    assert e.grid_meta.h_out * e.grid_meta.w_out_tiles > 1
    diags = check_conv_trace(build_conv_trace(e), plan.layers[0].strategy,
                             hw.size_mem, layer=0)
    assert diags == []


@pytest.mark.parametrize("c_in, hw_in, n, k", [(3, 32, 8, 4), (8, 8, 136, 2)])
def test_stride_equals_kernel_convs_run(c_in, hw_in, n, k):
    layer = _conv(c_in, hw_in, n, k, k)
    spec = ConvSpec(**layer)
    plan = plan_layers([layer], HardwareModel(nbop_pe=1 << 20),
                       name="patchify")
    e = emit_layer_kernel(plan.layers[0])
    rng = np.random.default_rng(k)
    x = jnp.asarray(rng.standard_normal((hw_in, hw_in, c_in)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, c_in, n)), jnp.float32)
    np.testing.assert_allclose(e.run_hwc(x, w, interpret=True),
                               _lax_conv(x, w, k), rtol=0, atol=1e-4)


# ---------------------------------------------------------------- graph

# A ConvNeXt in small: a 4x4/4 patchify stem and its LayerNorm, a block
# (depthwise 7x7 and LayerNorm, 1x1 expansion and GELU, 1x1 back and the
# residual add), a LayerNorm and a 2x2/2 downsample to 136 channels (two
# lane tiles a pixel), a second block, and the head: global average pool,
# LayerNorm and a 1x1 FC.
SMALL = [
    _conv(3, 16, 8, 4, 4, input=-1, ln="out"),                      # 0: 4x4
    _conv(1, 10, 8, 7, 1, input=0, groups=8, pad=3, ln="out"),      # 1
    _conv(8, 4, 32, 1, 1, input=1, gelu=True),                      # 2
    _conv(32, 4, 8, 1, 1, input=2, add=0),                          # 3
    _conv(8, 4, 136, 2, 2, input=3, ln="in"),                       # 4: 2x2
    _conv(1, 8, 136, 7, 1, input=4, groups=136, pad=3, ln="out"),   # 5
    _conv(136, 2, 16, 1, 1, input=5, gelu=True),                    # 6
    _conv(16, 2, 136, 1, 1, input=6, add=4),                        # 7
    _conv(136, 1, 10, 1, 1, input=7, pool="avg_global", ln="in"),   # 8
]


def _weights(layers, seed):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(
        (la["n_kernels"], la["c_in"], la["h_k"], la["w_k"]))
        / np.sqrt(la["c_in"] * la["h_k"] * la["w_k"]), jnp.float32)
        for la in layers]


def _plain_forward(x, weights):
    """SMALL's forward pass, (C, H, W), written out layer by layer."""
    def conv(h, w, s, pad=0, groups=1):
        return lax.conv_general_dilated(
            h[None], w, (s, s), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=groups,
            precision=lax.Precision.HIGHEST)[0]

    def ln(h):
        mu = h.mean(axis=0)
        return (h - mu) / jnp.sqrt(((h - mu) ** 2).mean(axis=0) + 1e-6)

    def gelu(h):
        return jax.nn.gelu(h, approximate=False)

    stem = ln(conv(x, weights[0], 4))
    h = ln(conv(stem, weights[1], 1, pad=3, groups=8))
    block1 = conv(gelu(conv(h, weights[2], 1)), weights[3], 1) + stem
    down = conv(ln(block1), weights[4], 2)
    h = ln(conv(down, weights[5], 1, pad=3, groups=136))
    block2 = conv(gelu(conv(h, weights[6], 1)), weights[7], 1) + down
    return conv(ln(block2.mean(axis=(1, 2), keepdims=True)), weights[8], 1)


def _plan_small():
    return plan_layers(SMALL, HardwareModel(nbop_pe=1 << 20, size_mem=1 << 16),
                       name="small-convnext")


def test_small_convnext_matches_a_plain_forward():
    plan = _plan_small()
    x = jnp.asarray(np.random.default_rng(3).standard_normal((3, 16, 16)),
                    jnp.float32)
    ws = _weights(SMALL, 4)
    out = execute_network(plan, x, ws)
    exp = _plain_forward(x, ws)
    assert out.shape == exp.shape == (10, 1, 1)
    np.testing.assert_allclose(out, exp, rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(exp))))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _walk(sub)


def test_a_trace_counts_and_names_the_layernorms_gelus_and_depthwise():
    plan = _plan_small()
    ws = [jax.ShapeDtypeStruct(w.shape, jnp.float32)
          for w in _weights(SMALL, 0)]
    x = jax.ShapeDtypeStruct((3, 16, 16), jnp.float32)
    names = ("executor/depthwise", "executor/layernorms", "executor/gelus",
             "executor/joins", "executor/pools", "executor/traces")
    jax.clear_caches()
    before = {n: REGISTRY.get(n) for n in names}
    closed = jax.make_jaxpr(lambda x, ws: execute_network(
        plan, x, ws, interpret=True))(x, ws)
    counted = {n: REGISTRY.get(n) - before[n] for n in names}
    assert counted == {"executor/depthwise": 2, "executor/layernorms": 5,
                       "executor/gelus": 2, "executor/joins": 2,
                       "executor/pools": 1, "executor/traces": 1}
    scopes = [str(e.source_info.name_stack) for e in _walk(closed.jaxpr)]
    assert any("executor.layernorm" in s for s in scopes)
    assert any("executor.gelu" in s for s in scopes)
    assert any(e.primitive.name == "erf" for e in _walk(closed.jaxpr)
               if "executor.gelu" in str(e.source_info.name_stack))


@pytest.mark.parametrize("name", ["resnet8", "lenet5"])
def test_dense_chains_trace_no_depthwise_layernorm_or_gelu(name):
    specs = list(NETWORKS[name])
    plan = plan_emitable_network(specs, network_budget(specs), name=name)
    s0 = specs[0]
    x = jax.ShapeDtypeStruct((s0.c_in, s0.h_in, s0.w_in), jnp.float32)
    ws = [jax.ShapeDtypeStruct((s.c_out, s.c_in, s.h_k, s.w_k), jnp.float32)
          for s in specs]
    names = ("executor/depthwise", "executor/layernorms", "executor/gelus",
             "executor/traces")
    jax.clear_caches()
    before = [REGISTRY.get(n) for n in names]
    jax.eval_shape(lambda x, ws: execute_network(plan, x, ws), x, ws)
    assert [REGISTRY.get(n) - b for n, b in zip(names, before)] == \
        [0, 0, 0, 1]


@pytest.mark.parametrize("change, match", [
    ({"ln": "mid"}, "ln must be"),
    ({"ln": True}, "ln must be"),
    ({"gelu": True, "relu": True}, "one"),
    ({"gelu": "yes"}, "gelu must be"),
    ({"groups": 4}, "groups=4"),
    ({"groups": True}, "groups must be"),
])
def test_the_graph_refuses_unknown_norms_two_activations_and_groups(
        change, match):
    layers = [dict(la) for la in SMALL]
    layers[1].update(change)
    with pytest.raises(GraphError, match=match):
        plan_layers(layers, HardwareModel(nbop_pe=1 << 20), name="bad")


def test_a_depthwise_layer_must_get_its_groups_channels():
    layers = [dict(la) for la in SMALL]
    layers[1]["groups"] = layers[1]["n_kernels"] = 4
    with pytest.raises(GraphError, match="8 channels feed a layer that "
                                         "expects 4"):
        plan_layers(layers[:2], HardwareModel(nbop_pe=1 << 20), name="bad")
