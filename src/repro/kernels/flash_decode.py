"""Pallas TPU kernel: decode attention as an S1 offloading schedule.

One decoded token attends to a long KV cache.  In the paper's terms
(DESIGN.md §4): the query block is the *kernel set* Λ — loaded once, resident
for every step (constant index_map -> Pallas revisiting); the KV cache is the
input tensor, cut into disjoint ``bkv``-sized *patch groups* (stride == block
size, so no halo); each grid step loads one KV block (I_slice, action a4),
computes (a6) with an online-softmax accumulator held on-chip, and the single
output block is written back once at the end (W at the last step, as Def 2
requires).  ``core.planner.plan_decode_attention`` chooses ``bkv`` under the
VMEM budget.

Layout: q (G, D) — the G = H_q/H_kv grouped query heads of one KV head;
k/v (S, D).  Batch and KV heads are vmapped in ``ops.decode_attention``.
A padded cache is handled with a length scalar: positions >= length are
masked before the softmax.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import KernelShapeError, resolve_interpret

_NEG_INF = -1e30


def decode_specs(g: int, d: int, s: int, bkv: int):
    """Grid + index_maps of the decode schedule, shared with the static
    checker: q and the output block are resident (constant maps), K/V
    stream one disjoint ``bkv`` block per step."""
    if d <= 0 or s <= 0 or bkv <= 0 or s % bkv:
        raise KernelShapeError(
            f"KV length {s} must be a positive multiple of bkv={bkv} "
            f"(ops.decode_attention pads)")
    kv_tiles = s // bkv
    grid = (kv_tiles,)

    def qmap(i, *_):
        return (0, 0)

    def kvmap(i, *_):
        return (i, 0)

    def omap(i, *_):
        return (0, 0)

    return grid, qmap, kvmap, omap


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, bkv: int, kv_tiles: int,
                   scale: float):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)              # (G, D) resident
    k = k_ref[...].astype(jnp.float32)              # (bkv, D) streamed
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = step * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < len_ref[0], s, _NEG_INF)

    m_prev = m_ref[...]                             # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                          # (G, bkv)
    alpha = jnp.exp(m_prev - m_new)                 # (G, 1)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(step == kv_tiles - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     length: jax.Array | int | None = None, *,
                     bkv: int = 512,
                     interpret: bool | None = None) -> jax.Array:
    """q (G, D), k/v (S, D), optional valid ``length`` -> (G, D)."""
    g, d = q.shape
    s, d2 = k.shape
    if d != d2:
        raise KernelShapeError(f"q has head dim {d} but k has {d2}")
    grid, qmap, kvmap, omap = decode_specs(g, d, s, bkv)
    kv_tiles = s // bkv
    if length is None:
        length = s
    length = jnp.asarray(length, jnp.int32).reshape(1)
    kernel = functools.partial(
        _decode_kernel, bkv=bkv, kv_tiles=kv_tiles,
        scale=1.0 / (d ** 0.5))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((g, d), qmap),      # q resident (Λ)
            pl.BlockSpec((bkv, d), kvmap),   # K patch group
            pl.BlockSpec((bkv, d), kvmap),   # V patch group
        ],
        out_specs=pl.BlockSpec((g, d), omap),
        scratch_shapes=[pltpu.VMEM((g, d), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32),
                        pltpu.VMEM((g, 1), jnp.float32)])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(length, q, k, v)
