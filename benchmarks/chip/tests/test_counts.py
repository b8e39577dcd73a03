"""The operation and byte counts against hand counts."""
import json

import pytest

import counts
import run

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _config(name):
    return json.loads((run.HERE / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, macs, nbytes", [
    # resnet8: 442,368 + 2 x 2,359,296 + 1,179,648 + 2,359,296
    #          + 1,179,648 + 2,359,296 MACs; 213,500 f32 elements read
    #          or written once.
    ("resnet8-f32", 12_238_848, 854_000),
    # lenet5: 1*25*6*28*28 + 6*25*16*10*10 MACs; (1024 + 150 + 4704)
    #         + (1176 + 2400 + 1600) f32 elements.
    ("lenet5-f32", 357_600, 44_216),
])
def test_network_counts(name, macs, nbytes):
    cfg = _config(name)
    assert counts.network_macs(cfg) == macs
    assert counts.network_flops(cfg) == 2 * macs
    assert counts.network_bytes(cfg) == nbytes


def test_least_time_is_the_larger_bound_per_layer():
    cfg = _config("resnet8-f32")
    # every resnet8 layer is memory-bound on a v5e at batch 1
    assert counts.least_seconds(cfg, PEAK) == pytest.approx(854_000 / 819e9)
    layer = {"c_in": 64, "h_in": 10, "w_in": 10, "n_kernels": 64,
             "h_k": 3, "w_k": 3, "s_h": 1, "s_w": 1}
    compute_bound = dict(PEAK, hbm_bytes_per_s=1e18)
    assert counts.least_seconds({"dtype": "float32", "layers": [layer]},
                                compute_bound) == \
        pytest.approx(2 * counts.layer_macs(layer) / 197e12)


def test_strided_output_size():
    layer = {"c_in": 2, "h_in": 9, "w_in": 8, "n_kernels": 4,
             "h_k": 3, "w_k": 2, "s_h": 2, "s_w": 3}
    assert counts.out_hw(layer) == (4, 3)
    assert counts.layer_macs(layer) == 2 * 3 * 2 * 4 * 4 * 3
