"""The ResNet-50 v1.5 configuration: its shapes against the published
stage table, its counts, its reference against the program on a shrunk
copy (CPU, interpret mode), and the reading of ``glue_us``."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import counts
import run

CONFIG = run.HERE / "configs" / "resnet50-f32.json"
#: He et al. Table 1, the 50-layer column: (width, blocks) per stage.
STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))


def _config():
    return json.loads(CONFIG.read_text())


def _stage_table(image=224, stem=64, expand=4, classes=1000):
    """Shape keys of ResNet-50 v1.5 in file order, from the stage table:
    the 7x7/2 stem on the padded image, then each bottleneck (a 1x1
    projection first where a stage opens, the stride on the 3x3), then
    the FC as a 1x1 conv on the pooled 1x1 map.  Inputs are padded:
    each 3x3 conv reads its map plus one a side."""
    def conv(c, hw, n, k, s):
        return {"c_in": c, "h_in": hw, "w_in": hw, "n_kernels": n,
                "h_k": k, "w_k": k, "s_h": s, "s_w": s}
    layers = [conv(3, image + 6, stem, 7, 2)]
    c, hw = stem, image // 4
    for stage, (width, blocks) in enumerate(STAGES):
        for b in range(blocks):
            s = 2 if stage and not b else 1
            if not b:
                layers.append(conv(c, hw, width * expand, 1, s))
            layers += [conv(c, hw, width, 1, 1),
                       conv(width, hw + 2, width, 3, s),
                       conv(width, hw // s, width * expand, 1, 1)]
            c, hw = width * expand, hw // s
    return layers + [conv(c, 1, classes, 1, 1)]


def test_the_config_counts_resnet50():
    cfg = _config()
    assert len(cfg["layers"]) == 54
    assert counts.network_macs(cfg) == 4_089_184_256
    assert sum(la["n_kernels"] * la["c_in"] * la["h_k"] * la["w_k"]
               for la in cfg["layers"]) == 25_502_912
    assert cfg["reduced"] == [] and cfg["reference"] == "resnet_graph"


def test_the_shape_keys_follow_the_stage_table():
    shapes = [{k: la[k] for k in run.SHAPE_KEYS}
              for la in _config()["layers"]]
    assert shapes == _stage_table()


def test_the_reference_refuses_a_key_it_does_not_read():
    reference = run.load_module("references", "resnet_graph")
    cfg = _config()
    cfg["layers"][3]["groups"] = 32
    with pytest.raises(ValueError, match="groups"):
        reference.make_forward(cfg)


def _shrunk(cfg, image=38, divisor=16):
    """``cfg``'s graph with every width cut by ``divisor`` and the image
    to ``image`` a side, each layer's input shape walked from the graph
    keys."""
    layers, outs = [], []
    for k, la in enumerate(cfg["layers"]):
        la = dict(la)
        src = la.get("input", k - 1)
        c, h = (3, image) if src < 0 else outs[src]
        if "pool" in la:
            h = 1 if la["pool"] == "avg_global" else (h - 1) // 2 + 1
        h += 2 * la.get("pad", 0)
        la.update(c_in=c, h_in=h, w_in=h,
                  n_kernels=max(1, la["n_kernels"] // divisor))
        layers.append(la)
        outs.append((la["n_kernels"], (h - la["h_k"]) // la["s_h"] + 1))
    return dict(cfg, layers=layers)


def test_the_reference_matches_the_program_on_a_shrunk_copy():
    from repro.core.cost_model import HardwareModel
    from repro.kernels.emit import execute_network, plan_layers
    cfg = _shrunk(_config())
    hw = HardwareModel(nbop_pe=cfg["budget"]["nbop_pe"],
                       size_mem=cfg["budget"]["size_mem"])
    plan = plan_layers(cfg["layers"], hw, name="resnet50-shrunk")
    weights, images = run.make_inputs(cfg, 256, 2**33 + 16)
    forward = run.reference_forward(cfg)
    xs = jnp.stack(images[:2])
    outs = np.stack([np.asarray(execute_network(plan, x, weights))
                     for x in xs])
    refs = np.asarray(forward(xs, weights))
    assert outs.shape == refs.shape == (2, 62, 1, 1)
    assert run.max_rel_err(outs, refs) < 1e-5


@pytest.mark.parametrize("trace, value", [
    (None, None),
    ({"images": 0, "busy_s": 0.0, "conv_s": 0.0}, None),
    ({"images": 100, "busy_s": 0.5, "conv_s": 0.375}, 1250.0),
])
def test_glue_us_reads_the_device_time_outside_the_convs(trace, value):
    glue_us = run.load_module("metrics", "glue_us")
    got = glue_us.reduce({"trace": trace})
    if value is None:
        assert got is None
    else:
        assert got == pytest.approx(value)
