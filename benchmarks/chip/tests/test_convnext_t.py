"""The ConvNeXt-T configuration: its shapes against the published stage
table, its counts, its plan and kernel contracts, its reference against
the program on a shrunk copy (CPU, interpret mode), and the depthwise
counts and readings of ``dw_us`` and ``dw_roofline``."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import counts
import dw_counts
import run

CONFIG = run.HERE / "configs" / "convnext-t-f32.json"
#: Liu et al. 2022, ConvNeXt-T: (width, blocks) per stage.
STAGES = ((96, 3), (192, 3), (384, 9), (768, 3))
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config():
    return json.loads(CONFIG.read_text())


def _stage_table(image=224, expand=4, classes=1000):
    """Shape keys of ConvNeXt-T in file order, from the stage table: the
    4x4/4 stem, each stage's blocks (depthwise 7x7 on the map plus 3 a
    side, 1x1 to ``expand`` x the width, 1x1 back), a 2x2/2 downsample
    between stages, and the FC as a 1x1 conv on the pooled 1x1 map."""
    def conv(c, hw, n, k, s):
        return {"c_in": c, "h_in": hw, "w_in": hw, "n_kernels": n,
                "h_k": k, "w_k": k, "s_h": s, "s_w": s}
    layers = [conv(3, image, STAGES[0][0], 4, 4)]
    hw = image // 4
    for stage, (width, blocks) in enumerate(STAGES):
        if stage:
            layers.append(conv(STAGES[stage - 1][0], hw, width, 2, 2))
            hw //= 2
        for _ in range(blocks):
            layers += [conv(1, hw + 6, width, 7, 1),
                       conv(width, hw, width * expand, 1, 1),
                       conv(width * expand, hw, width, 1, 1)]
    return layers + [conv(STAGES[-1][0], 1, classes, 1, 1)]


def test_the_config_counts_convnext_t():
    cfg = _config()
    assert len(cfg["layers"]) == 59
    assert counts.network_macs(cfg) == 4_455_531_264
    assert sum(counts.layer_macs(cfg["layers"][k])
               for k in dw_counts.dw_layers(cfg)) == 105_106_176
    assert sum(la["n_kernels"] * la["c_in"] * la["h_k"] * la["w_k"]
               for la in cfg["layers"]) == 28_524_000
    assert max(la["n_kernels"] * la["c_in"] * la["h_k"] * la["w_k"]
               for la in cfg["layers"]) * 2 == cfg["budget"]["size_mem"]
    assert cfg["reduced"] == [] and cfg["reference"] == "convnext_graph"


def test_the_shape_keys_follow_the_stage_table():
    layers = _config()["layers"]
    shapes = [{k: la[k] for k in run.SHAPE_KEYS} for la in layers]
    assert shapes == _stage_table()
    dws = [la for la in layers if "groups" in la]
    assert len(dws) == 18
    assert all(la["groups"] == la["n_kernels"] and la["c_in"] == 1
               and la["pad"] == 3 and la["ln"] == "out" for la in dws)
    assert sum(la.get("gelu", False) for la in layers) == 18
    assert sum("ln" in la for la in layers) == 23


def test_the_config_plans_verified_and_every_kernel_passes_kerncheck():
    from repro.analysis.kerncheck import build_conv_trace, check_conv_trace
    from repro.kernels import emit
    plan = run.plan_network(_config(), emit)
    emitted = [emit.emit_layer_kernel(lp) for lp in plan.layers]
    assert [e.t_run for e in emitted] == [14] * 48 + [7] * 10 + [1]
    assert sum(e.grid_meta.h_out * e.grid_meta.w_out_tiles
               for e in emitted) == 3263
    for lp, e in zip(plan.layers, emitted):
        assert check_conv_trace(build_conv_trace(e), lp.strategy,
                                plan.hw.size_mem, layer=lp.index) == []


@pytest.mark.parametrize("change, match", [
    ({"shift": 1}, "does not read"),
    ({"groups": 48}, "groups=48"),
    ({"ln": "both"}, "ln"),
    ({"relu": True, "gelu": True}, "relu and gelu"),
])
def test_the_reference_refuses_what_it_does_not_read(change, match):
    reference = run.load_module("references", "convnext_graph")
    cfg = _config()
    cfg["layers"][1].update(change)
    with pytest.raises(ValueError, match=match):
        reference.make_forward(cfg)


def _shrunk(cfg, image=32, divisor=16):
    """``cfg``'s graph with every width cut by ``divisor`` and the image
    to ``image`` a side, each layer's input shape walked from the graph
    keys; a depthwise layer keeps one kernel a channel."""
    layers, outs = [], []
    for k, la in enumerate(cfg["layers"]):
        la = dict(la)
        src = la.get("input", k - 1)
        c, h = (3, image) if src < 0 else outs[src]
        if "pool" in la:
            h = 1
        h += 2 * la.get("pad", 0)
        if "groups" in la:
            la.update(c_in=1, groups=c, n_kernels=c)
        else:
            la.update(c_in=c, n_kernels=max(1, la["n_kernels"] // divisor))
        la.update(h_in=h, w_in=h)
        layers.append(la)
        outs.append((la["n_kernels"], (h - la["h_k"]) // la["s_h"] + 1))
    return dict(cfg, layers=layers)


def test_the_reference_matches_the_program_on_a_shrunk_copy():
    from repro.core.cost_model import HardwareModel
    from repro.kernels.emit import execute_network, plan_layers
    cfg = _shrunk(_config())
    hw = HardwareModel(nbop_pe=cfg["budget"]["nbop_pe"],
                       size_mem=cfg["budget"]["size_mem"])
    plan = plan_layers(cfg["layers"], hw, name="convnext-shrunk")
    weights, images = run.make_inputs(cfg, 256, 2**33 + 21)
    forward = run.reference_forward(cfg)
    xs = jnp.stack(images[:2])
    outs = np.stack([np.asarray(execute_network(plan, x, weights))
                     for x in xs])
    refs = np.asarray(forward(xs, weights))
    assert outs.shape == refs.shape == (2, 62, 1, 1)
    assert run.max_rel_err(outs, refs) < 1e-5
    # the control's three bf16 passes read further off than the program
    control = run.load_module("references", "convnext_graph").make_forward(
        cfg, passes=3)
    assert run.max_rel_err(np.asarray(control(xs, weights)), refs) > \
        10 * run.max_rel_err(outs, refs)


def test_dw_counts_reckon_the_depthwise_layers():
    cfg = _config()
    ks = dw_counts.dw_layers(cfg)
    assert ks == [1 + 3 * b for b in range(3)] + \
        [11 + 3 * b for b in range(3)] + [21 + 3 * b for b in range(9)] + \
        [49 + 3 * b for b in range(3)]
    # the first: 96 channels, 62x62 in, 7x7 taps, 56x56 out, f32
    assert dw_counts.dw_bytes(cfg["layers"][1], "float32") == \
        96 * (62 * 62 + 49 + 56 * 56) * 4
    assert sum(dw_counts.dw_bytes(cfg["layers"][k], "float32")
               for k in ks) == 24_057_216
    # HBM bounds every depthwise layer: 24.06 MB at 819 GB/s
    assert dw_counts.dw_least_seconds(cfg, PEAK) == \
        pytest.approx(24_057_216 / 819e9)
    assert dw_counts.dw_layers({"layers": [{"c_in": 3}]}) == []


def _trace(layer_s, images=4):
    return {"images": images, "layer_s": layer_s, "conv_s": sum(layer_s),
            "busy_s": sum(layer_s)}


def test_dw_us_and_dw_roofline_read_the_depthwise_layers():
    cfg = {"dtype": "float32", "layers": [
        {"c_in": 3, "h_in": 16, "w_in": 16, "n_kernels": 8, "h_k": 4,
         "w_k": 4, "s_h": 4, "s_w": 4},
        {"c_in": 1, "h_in": 10, "w_in": 10, "n_kernels": 8, "h_k": 7,
         "w_k": 7, "s_h": 1, "s_w": 1, "groups": 8},
        {"c_in": 8, "h_in": 4, "w_in": 4, "n_kernels": 8, "h_k": 1,
         "w_k": 1, "s_h": 1, "s_w": 1}]}
    ctx = {"cfg": cfg, "peak": PEAK, "trace": _trace([1e-3, 2e-5, 5e-4])}
    dw_us = run.load_module("metrics", "dw_us")
    dw_roofline = run.load_module("metrics", "dw_roofline")
    assert dw_us.reduce(ctx) == pytest.approx(5.0)     # 20 us over 4 images
    least = 8 * (100 + 49 + 16) * 4 / 819e9            # bytes bound it
    assert least > 2 * 8 * 49 * 16 / 197e12
    assert dw_roofline.reduce(ctx) == pytest.approx(least / 5e-6 * 100)


@pytest.mark.parametrize("ctx", [
    {"trace": None},
    {"trace": _trace([1e-3, 2e-5], images=0)},
    {"trace": dict(_trace([1e-3, 2e-5]), layer_s=None)},
    {"trace": _trace([1e-3, 2e-5]), "dense": True},
])
def test_dw_metrics_read_nothing_without_depthwise_kernel_times(ctx):
    layers = [{"c_in": 3}, {"c_in": 1, "groups": 8}]
    if ctx.pop("dense", False):
        layers = [{"c_in": 3}, {"c_in": 8}]
    ctx = dict(ctx, cfg={"dtype": "float32", "layers": layers}, peak=PEAK)
    for name in ("dw_us", "dw_roofline"):
        assert run.load_module("metrics", name).reduce(ctx) is None
