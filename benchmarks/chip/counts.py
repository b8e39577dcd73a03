"""Work a configuration's conv layers must do, from its shapes alone.

Every layer of a configuration is a conv layer, and only its shape keys
(``c_in, h_in, w_in, n_kernels, h_k, w_k, s_h, s_w``) are read.  What
other keys tell the program, such as residual adds, activations and
pools, is not counted.

The roofline and the utilization divide these counts by measured time,
so they count what the convolution needs and nothing a kernel adds: no
lane padding, no re-fetched window columns.  Bytes are each layer's
logical input, kernels and output in the configuration's dtype, read or
written once.
"""
from __future__ import annotations

import numpy as np


def out_hw(layer: dict) -> tuple[int, int]:
    """(H_out, W_out) of a valid (unpadded) convolution."""
    return ((layer["h_in"] - layer["h_k"]) // layer["s_h"] + 1,
            (layer["w_in"] - layer["w_k"]) // layer["s_w"] + 1)


def layer_macs(layer: dict) -> int:
    h_out, w_out = out_hw(layer)
    return (layer["c_in"] * layer["h_k"] * layer["w_k"]
            * layer["n_kernels"] * h_out * w_out)


def layer_bytes(layer: dict, dtype: str) -> int:
    h_out, w_out = out_hw(layer)
    elements = (layer["c_in"] * layer["h_in"] * layer["w_in"]
                + layer["n_kernels"] * layer["c_in"] * layer["h_k"]
                * layer["w_k"]
                + layer["n_kernels"] * h_out * w_out)
    return elements * np.dtype(dtype).itemsize


def network_macs(cfg: dict) -> int:
    return sum(layer_macs(layer) for layer in cfg["layers"])


def network_flops(cfg: dict) -> int:
    """Operations per image: one multiply and one add per MAC."""
    return 2 * network_macs(cfg)


def network_bytes(cfg: dict) -> int:
    return sum(layer_bytes(layer, cfg["dtype"]) for layer in cfg["layers"])


def least_seconds(cfg: dict, peak: dict) -> float:
    """The least time one image's conv layers could take on a chip with
    ``peak``: the larger of operations over peak FLOP/s and bytes over
    peak HBM bandwidth, layer by layer, summed."""
    return sum(max(2 * layer_macs(layer) / peak["bf16_flops_per_s"],
                   layer_bytes(layer, cfg["dtype"])
                   / peak["hbm_bytes_per_s"])
               for layer in cfg["layers"])
