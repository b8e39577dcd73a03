"""Jit'd public wrappers around the block-GeMM and flash-decode kernels.

Each wrapper: pads to kernel-friendly shapes, consults ``core.planner``
for the tiles when the caller does not pin them, dispatches to the Pallas
kernel (interpret mode off the TPU, see ``resolve_interpret``), and
unpads.  ``ref.py`` holds the oracles; tests sweep shapes/dtypes and
assert_allclose kernel vs oracle.  The conv kernel has no wrapper here:
``kernels.emit`` plans and runs it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import planner
from repro.kernels import KernelShapeError
from repro.kernels import block_matmul as _bm
from repro.kernels import flash_decode as _fd


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "order", "plan"))
def matmul(a: jax.Array, b: jax.Array, *, bm: int | None = None,
           bn: int | None = None, bk: int | None = None,
           order: str | None = None, plan: bool = True) -> jax.Array:
    """Planner-scheduled block GeMM."""
    m, k = a.shape
    _, n = b.shape
    if bm is None or bn is None or bk is None or order is None:
        p = planner.plan_matmul(m, n, k, dtype_bytes=a.dtype.itemsize)
        bm = bm or min(p.tiles["bm"], 1 << (max(m, 8) - 1).bit_length())
        bn = bn or min(p.tiles["bn"], 1 << (max(n, 8) - 1).bit_length())
        bk = bk or min(p.tiles["bk"], 1 << (max(k, 8) - 1).bit_length())
        order = order or p.order
    a = _pad_to(_pad_to(a, 0, bm), 1, bk)
    b = _pad_to(_pad_to(b, 0, bk), 1, bn)
    out = _bm.block_matmul(a, b, bm=bm, bn=bn, bk=bk, order=order)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bkv",))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array | None = None, *,
                     bkv: int | None = None) -> jax.Array:
    """Batched GQA decode attention over a (padded) KV cache.

    q: (B, H_q, D); k/v: (B, S, H_kv, D); lengths: (B,) valid cache lengths.
    Returns (B, H_q, D).
    """
    b, h_q, d = q.shape
    _, s, h_kv, _ = k.shape
    if h_q % h_kv != 0:
        raise KernelShapeError(
            f"GQA needs h_q={h_q} divisible by h_kv={h_kv}")
    g = h_q // h_kv
    if bkv is None:
        p = planner.plan_decode_attention(s, d, g, q.dtype.itemsize)
        bkv = min(p.tiles["bkv"], s)
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)

    qg = q.reshape(b, h_kv, g, d)
    kg = jnp.moveaxis(k, 2, 1)           # (B, H_kv, S, D)
    vg = jnp.moveaxis(v, 2, 1)

    single = functools.partial(_fd.decode_attention, bkv=bkv)
    per_head = jax.vmap(single, in_axes=(0, 0, 0, None))     # over H_kv
    per_batch = jax.vmap(per_head, in_axes=(0, 0, 0, 0))     # over B
    out = per_batch(qg, kg, vg, lengths)
    return out.reshape(b, h_q, d)
