"""Plain float32 reference of a ConvNeXt-style network of convolutions.

It reads every key ``resnet_graph`` reads (``input``, ``pool``, ``pad``,
``add``, ``relu``), with the same meaning, and three more:

* ``groups``: the conv's channel groups, as PyTorch's ``groups``: 1, a
  dense conv (``conv_chain``'s), or ``n_kernels`` with ``c_in`` 1, a
  depthwise conv: output channel n is the sum over kernel taps of input
  channel n times that tap's weight, a plain channel-wise product;
* ``ln``: LayerNorm over channels, epsilon ``EPS``, biased variance,
  identity affine: ``"in"`` on the tensor the conv reads, after the
  pool and before the pad, ``"out"`` on the layer's output, after the
  add and the activation;
* ``gelu``: the exact GELU, ``x * Phi(x)`` through erf, after the add.

A layer's output is its value after the add, the activation and an
``"out"`` LayerNorm; the network's is the last layer's.  It imports
nothing of the program under test and is written apart from it.  It
refuses a key it does not read, a ``groups`` or ``ln`` it does not know,
``relu`` with ``gelu``, and a layer whose input, once pooled and padded,
is not the shape the layer states.

``passes=3`` is the control, as in ``conv_chain``: the dense
contractions and the depthwise products take float32 operands as a
bfloat16 high and low part and sum the three larger cross products.

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
    "convnext_graph_resnet_graph", Path(__file__).with_name("resnet_graph.py"))
resnet_graph = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(resnet_graph)
conv_chain = resnet_graph.conv_chain

#: The keys of a layer this reference reads.
READS = resnet_graph.READS | {"groups", "ln", "gelu"}
#: LayerNorm's epsilon, ConvNeXt's.
EPS = 1e-6
#: Images a block of the check runs at once.
BLOCK = 16


def check_graph(cfg: dict) -> None:
    """ValueError where a layer holds a key this reference does not read
    or a value of one it does not know, reads or adds a layer that is
    not an earlier one, or does not get the input shape it states."""
    shapes = []       # each layer's output (C, H, W)
    first = cfg["layers"][0]
    for k, layer in enumerate(cfg["layers"]):
        unread = sorted(set(layer) - READS)
        if unread:
            raise ValueError(f"convnext_graph does not read {unread} "
                             f"(layer {k})")
        groups = layer.get("groups", 1)
        if groups != 1 and (groups != layer["n_kernels"]
                            or layer["c_in"] != 1):
            raise ValueError(f"layer {k}: groups={groups} is neither a "
                             f"dense nor a depthwise conv")
        if layer.get("ln") not in (None, "in", "out"):
            raise ValueError(f"layer {k}: unknown ln {layer['ln']!r}")
        if layer.get("relu") and layer.get("gelu"):
            raise ValueError(f"layer {k}: relu and gelu together")
        src = layer.get("input", k - 1)
        if not -1 <= src < k:
            raise ValueError(f"layer {k} reads layer {src}")
        c, h, w = ((first["c_in"], first["h_in"], first["w_in"])
                   if src < 0 else shapes[src])
        if k == 0 and (src != -1 or "pool" in layer or layer.get("pad")):
            raise ValueError("layer 0 reads the image as it is")
        if "pool" in layer:
            if layer["pool"] == "max2x2" and (h % 2 or w % 2):
                raise ValueError(f"layer {k}: cannot 2x2-pool {h}x{w}")
            h, w = resnet_graph._pooled_hw(layer["pool"], h, w)
        pad = layer.get("pad", 0)
        want = (layer["c_in"] * groups, layer["h_in"], layer["w_in"])
        if (c, h + 2 * pad, w + 2 * pad) != want:
            raise ValueError(f"layer {k} gets a {c}x{h + 2 * pad}x"
                             f"{w + 2 * pad} input, not the "
                             f"{want[0]}x{want[1]}x{want[2]} it states")
        out = (layer["n_kernels"],
               (layer["h_in"] - layer["h_k"]) // layer["s_h"] + 1,
               (layer["w_in"] - layer["w_k"]) // layer["s_w"] + 1)
        if "add" in layer:
            j = layer["add"]
            if not 0 <= j < k or shapes[j] != out:
                raise ValueError(f"layer {k} cannot add layer {j}")
        shapes.append(out)


def layer_norm(y):
    """(B, C, H, W) normalised over C at each pixel."""
    mean = y.mean(axis=1, keepdims=True)
    var = jnp.square(y - mean).mean(axis=1, keepdims=True)
    return (y - mean) / jnp.sqrt(var + EPS)


def gelu(y):
    return 0.5 * y * (1.0 + lax.erf(y / jnp.sqrt(2.0)))


def _product(x, w, passes: int | None):
    """x * w in float32, or in three bfloat16 passes for the control."""
    if passes is None:
        return x * w
    if passes != 3:
        raise ValueError(f"unsupported pass count {passes}")
    x_hi, x_lo = conv_chain._split_bf16(x)
    w_hi, w_lo = conv_chain._split_bf16(w)
    return x_hi * w_lo + x_lo * w_hi + x_hi * w_hi


def depthwise(x, w, s_h: int, s_w: int, passes: int | None = None):
    """Depthwise valid convolution: x (B, C, H, W), w (C, 1, Hk, Wk)
    -> (B, C, H_out, W_out), the sum over taps of channel-wise
    products."""
    _, _, h, wd = x.shape
    _, _, h_k, w_k = w.shape
    h_out = (h - h_k) // s_h + 1
    w_out = (wd - w_k) // s_w + 1
    out = jnp.zeros((x.shape[0], w.shape[0], h_out, w_out), jnp.float32)
    for kh in range(h_k):
        for kw in range(w_k):
            xs = x[:, :, kh:kh + (h_out - 1) * s_h + 1:s_h,
                   kw:kw + (w_out - 1) * s_w + 1:s_w]
            out = out + _product(xs, w[None, :, 0, kh, kw, None, None],
                                 passes)
    return out


def forward(cfg: dict, x, weights, passes: int | None = None):
    """The network on a batch x (B, C, H, W) in float32."""
    image = x.astype(jnp.float32)
    outs = []
    for k, (layer, w) in enumerate(zip(cfg["layers"], weights)):
        src = layer.get("input", k - 1)
        h = image if src < 0 else outs[src]
        if "pool" in layer:
            h = resnet_graph.pool(h, layer["pool"])
        if layer.get("ln") == "in":
            h = layer_norm(h)
        pad = layer.get("pad", 0)
        if pad:
            h = jnp.pad(h, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
        conv = (depthwise if layer.get("groups", 1) != 1
                else conv_chain.conv)
        h = conv(h, w.astype(jnp.float32), layer["s_h"], layer["s_w"],
                 passes)
        if "add" in layer:
            h = h + outs[layer["add"]]
        if layer.get("relu", False):
            h = jnp.maximum(h, 0.0)
        if layer.get("gelu", False):
            h = gelu(h)
        if layer.get("ln") == "out":
            h = layer_norm(h)
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
