"""Work a configuration's depthwise conv layers must do, from their shapes.

A depthwise layer is one whose ``groups`` key is above 1 (``groups ==
n_kernels``, ``c_in == 1``): each of its C = ``n_kernels`` kernels reads
one channel of a C-channel input.  ``counts.layer_macs`` is exact for
it, since ``c_in`` is the channels a kernel reads; its bytes are counted
here, with the input's C channels: C·H_in·W_in + C·h_k·w_k +
C·H_out·W_out elements in the configuration's dtype, each read or
written once.  Like ``counts``, nothing a kernel adds is counted: no
lane padding, no window re-fetched.
"""
from __future__ import annotations

import numpy as np

import counts


def dw_layers(cfg: dict) -> list[int]:
    """Indices of ``cfg``'s depthwise layers, in file order."""
    return [k for k, layer in enumerate(cfg["layers"])
            if layer.get("groups", 1) > 1]


def dw_bytes(layer: dict, dtype: str) -> int:
    h_out, w_out = counts.out_hw(layer)
    c = layer["c_in"] * layer["groups"]
    elements = c * (layer["h_in"] * layer["w_in"]
                    + layer["h_k"] * layer["w_k"] + h_out * w_out)
    return elements * np.dtype(dtype).itemsize


def dw_least_seconds(cfg: dict, peak: dict) -> float:
    """The least time one image's depthwise layers could take on a chip
    with ``peak``: per layer the larger of operations over peak FLOP/s
    and bytes over HBM bandwidth, summed."""
    layers = cfg["layers"]
    return sum(max(2 * counts.layer_macs(layers[k])
                   / peak["bf16_flops_per_s"],
                   dw_bytes(layers[k], cfg["dtype"])
                   / peak["hbm_bytes_per_s"])
               for k in dw_layers(cfg))


def dw_seconds(cfg: dict, trace: dict | None) -> float | None:
    """Measured device seconds per image of the depthwise layers' kernels
    in a reduced trace (``xplane.reduce``), None where the trace maps no
    kernel to a layer or the configuration has no depthwise layer."""
    if trace is None or not trace["images"] or trace["layer_s"] is None:
        return None
    ks = dw_layers(cfg)
    if not ks:
        return None
    return sum(trace["layer_s"][k] for k in ks) / trace["images"]
