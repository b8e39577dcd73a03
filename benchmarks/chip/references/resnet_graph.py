"""Plain float32 reference of a network of convolutions joined as a graph.

Each layer is ``conv_chain``'s convolution (a sum over kernel taps of
one channel contraction at full float32 precision, or in three bf16
passes for the control) on the tensor its graph keys name:

* ``input``: the layer whose output it reads, -1 for the image
  (default: the layer before);
* ``pool``: ``"max2x2"``, ``"max3x3s2p1"`` (3x3 window, stride 2, one
  row and column of -inf on each side) or ``"avg_global"``, applied to
  that tensor;
* ``pad``: zero rows and columns added on each side after the pool;
* ``add``: an earlier layer whose output is added to the convolution's;
* ``relu``: ReLU after the add.

A layer's output is its value after the add and the ReLU; the network's
is the last layer's.  It imports nothing of the program under test and
is written apart from it.  It refuses a key it does not read, and a
layer whose input, once pooled and padded, is not the shape the layer
states: where this reading and the program's could differ, there is no
reference.

The check compares 128 images; they run through the network in blocks
of ``BLOCK``, one block at a time, so that the activations of a block
fit the device beside the run's own buffers.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import lax

_spec = importlib.util.spec_from_file_location(
    "resnet_graph_conv_chain", Path(__file__).with_name("conv_chain.py"))
conv_chain = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(conv_chain)

#: The keys of a layer this reference reads.
READS = conv_chain.READS | {"input", "pool", "pad", "add", "relu"}
#: Images a block of the check runs at once.
BLOCK = 16


def pool(y, kind: str):
    """(B, C, H, W) pooled by ``kind``."""
    b, c, h, w = y.shape
    if kind == "max2x2":
        return y.reshape(b, c, h // 2, 2, w // 2, 2).max(axis=(3, 5))
    if kind == "max3x3s2p1":
        h_out, w_out = (h - 1) // 2 + 1, (w - 1) // 2 + 1
        yp = jnp.pad(y, ((0, 0), (0, 0), (1, 1), (1, 1)),
                     constant_values=-jnp.inf)
        out = None
        for i in range(3):
            for j in range(3):
                tap = yp[:, :, i:i + 2 * (h_out - 1) + 1:2,
                         j:j + 2 * (w_out - 1) + 1:2]
                out = tap if out is None else jnp.maximum(out, tap)
        return out
    if kind == "avg_global":
        return y.mean(axis=(2, 3), keepdims=True)
    raise ValueError(f"unknown pool {kind!r}")


def _pooled_hw(kind: str, h: int, w: int) -> tuple[int, int]:
    if kind == "max2x2":
        return h // 2, w // 2
    if kind == "max3x3s2p1":
        return (h - 1) // 2 + 1, (w - 1) // 2 + 1
    if kind == "avg_global":
        return 1, 1
    raise ValueError(f"unknown pool {kind!r}")


def check_graph(cfg: dict) -> None:
    """ValueError where a layer holds a key this reference does not read,
    reads or adds a layer that is not an earlier one, or does not get
    the input shape it states."""
    shapes = []       # each layer's output (C, H, W)
    for k, layer in enumerate(cfg["layers"]):
        unread = sorted(set(layer) - READS)
        if unread:
            raise ValueError(f"resnet_graph does not read {unread} "
                             f"(layer {k})")
        src = layer.get("input", k - 1)
        if not -1 <= src < k:
            raise ValueError(f"layer {k} reads layer {src}")
        first = cfg["layers"][0]
        c, h, w = ((first["c_in"], first["h_in"], first["w_in"]) if src < 0
                   else shapes[src])
        if k == 0 and (src != -1 or "pool" in layer or layer.get("pad")):
            raise ValueError("layer 0 reads the image as it is")
        if "pool" in layer:
            if layer["pool"] == "max2x2" and (h % 2 or w % 2):
                raise ValueError(f"layer {k}: cannot 2x2-pool {h}x{w}")
            h, w = _pooled_hw(layer["pool"], h, w)
        pad = layer.get("pad", 0)
        if (c, h + 2 * pad, w + 2 * pad) != \
                (layer["c_in"], layer["h_in"], layer["w_in"]):
            raise ValueError(f"layer {k} gets a {c}x{h + 2 * pad}x"
                             f"{w + 2 * pad} input, not the "
                             f"{layer['c_in']}x{layer['h_in']}x"
                             f"{layer['w_in']} it states")
        out = (layer["n_kernels"],
               (layer["h_in"] - layer["h_k"]) // layer["s_h"] + 1,
               (layer["w_in"] - layer["w_k"]) // layer["s_w"] + 1)
        if "add" in layer:
            j = layer["add"]
            if not 0 <= j < k or shapes[j] != out:
                raise ValueError(f"layer {k} cannot add layer {j}")
        shapes.append(out)


def forward(cfg: dict, x, weights, passes: int | None = None):
    """The network on a batch x (B, C, H, W) in float32."""
    image = x.astype(jnp.float32)
    outs = []
    for k, (layer, w) in enumerate(zip(cfg["layers"], weights)):
        src = layer.get("input", k - 1)
        h = image if src < 0 else outs[src]
        if "pool" in layer:
            h = pool(h, layer["pool"])
        pad = layer.get("pad", 0)
        if pad:
            h = jnp.pad(h, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        h = conv_chain.conv(h, w.astype(jnp.float32), layer["s_h"],
                            layer["s_w"], passes)
        if "add" in layer:
            h = h + outs[layer["add"]]
        if layer.get("relu", False):
            h = jnp.maximum(h, 0.0)
        outs.append(h)
    return outs[-1]


def make_forward(cfg: dict, passes: int | None = None):
    """``forward`` for ``cfg`` as one function of (x, weights), run
    ``BLOCK`` images at a time; ValueError where ``check_graph`` refuses
    the configuration."""
    check_graph(cfg)

    @jax.jit
    def blocks(xs, ws):
        return lax.map(lambda xb: forward(cfg, xb, ws, passes), xs)

    def run(x, weights):
        n = x.shape[0]
        pad = -n % BLOCK
        xs = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        out = blocks(xs.reshape(-1, BLOCK, *x.shape[1:]), list(weights))
        return out.reshape(-1, *out.shape[2:])[:n]
    return run
