"""Plain float32 reference of a chain of valid convolutions.

Each layer is a sum over kernel taps of one channel contraction, at full
float32 precision; between layers a 2x2 max-pool where the previous map
is larger than the next input, then centred zero padding.  It imports
nothing of the program under test and is written apart from it, so a
fault in the program's glue or kernels cannot hide in both.

``passes=3`` is the control: every contraction takes float32 operands
as a bfloat16 high part plus a bfloat16 low part and sums the three
larger cross products, which is what ``Precision.HIGH`` does on a TPU,
spelled out so that it reads the same on any backend.  The parts are cut
with ``reduce_precision``: XLA may drop a float32 -> bfloat16 -> float32
round trip of casts as excess precision, which would leave the low part
zero and the control one pass.

It reads a layer's shape keys and nothing else, so it refuses a
configuration whose layers carry any other key: such a key tells the
program something (a join, an activation, a pool) that this chain would
leave out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: The keys of a layer this reference reads.
READS = frozenset(("c_in", "h_in", "w_in", "n_kernels", "h_k", "w_k",
                   "s_h", "s_w"))


def _contract(x, w, passes: int | None):
    """(B, C, H, W) x (N, C) -> (B, N, H, W) in f32."""
    spec = "bchw,nc->bnhw"
    if passes is None:
        return jnp.einsum(spec, x, w, precision=lax.Precision.HIGHEST)
    if passes != 3:
        raise ValueError(f"unsupported pass count {passes}")
    x_hi, x_lo = _split_bf16(x)
    w_hi, w_lo = _split_bf16(w)
    out = None
    for a, b in ((x_hi, w_lo), (x_lo, w_hi), (x_hi, w_hi)):
        part = jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _split_bf16(a):
    """float32 ``a`` as hi + lo, each exactly a bfloat16 value (in f32)."""
    hi = lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def conv(x, w, s_h: int, s_w: int, passes: int | None = None):
    """Valid convolution: x (B, C, H, W), w (N, C, Hk, Wk)
    -> (B, N, H_out, W_out)."""
    _, _, h, wd = x.shape
    _, _, h_k, w_k = w.shape
    h_out = (h - h_k) // s_h + 1
    w_out = (wd - w_k) // s_w + 1
    out = jnp.zeros((x.shape[0], w.shape[0], h_out, w_out), jnp.float32)
    for kh in range(h_k):
        for kw in range(w_k):
            xs = x[:, :, kh:kh + (h_out - 1) * s_h + 1:s_h,
                   kw:kw + (w_out - 1) * s_w + 1:s_w]
            out = out + _contract(xs, w[:, :, kh, kw], passes)
    return out


def adapt(y, layer: dict):
    """Previous output (B, C, H, W) -> the next ``layer``'s input."""
    b, c, h, w = y.shape
    if h > layer["h_in"] or w > layer["w_in"]:
        y = y.reshape(b, c, h // 2, 2, w // 2, 2).max(axis=(3, 5))
        h, w = h // 2, w // 2
    ph, pw = layer["h_in"] - h, layer["w_in"] - w
    return jnp.pad(y, ((0, 0), (0, 0), (ph // 2, ph - ph // 2),
                       (pw // 2, pw - pw // 2)))


def forward(cfg: dict, x, weights, passes: int | None = None):
    """The network on a batch x (B, C, H, W) in float32."""
    h = x.astype(jnp.float32)
    for k, (layer, w) in enumerate(zip(cfg["layers"], weights)):
        if k:
            h = adapt(h, layer)
        h = conv(h, w.astype(jnp.float32), layer["s_h"], layer["s_w"],
                 passes)
    return h


def make_forward(cfg: dict, passes: int | None = None):
    """``forward`` for ``cfg`` as one jitted function of (x, weights);
    ValueError where a layer holds a key this reference does not read."""
    for k, layer in enumerate(cfg["layers"]):
        unread = sorted(set(layer) - READS)
        if unread:
            raise ValueError(f"conv_chain reads only the shape keys; layer "
                             f"{k} also holds {unread}")
    return jax.jit(lambda x, ws: forward(cfg, x, ws, passes))
