"""Pallas TPU kernel: strategy-driven block GeMM (paper Sec 1.3 adaptation).

The paper notes its formalism applies to GeMM-based accelerators (TMMA/VTA)
with "slightly adapted" strategies: tiles of A/B/C play the role of patches
and kernels, and the loop order decides which operand is revisited (kept in
on-chip memory) between consecutive steps.  ``core.planner.plan_matmul``
enumerates tile shapes x loop orders under the paper's duration model and
this kernel executes the chosen plan:

  * order "...k" (k innermost)  — output-stationary: the C block is the
    resident set, A/B stream (S1 with C in the Λ role);
  * order "..m" / "..n" inner   — the A (resp. B) block is revisited across
    the inner sweep, C is read-modified-written.

Blocks are plain BlockSpecs (non-overlapping — no halo in GeMM), grid
dimension semantics mark k as "arbitrary" for TPU so the compiler may
software-pipeline the parallel dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import KernelShapeError, resolve_interpret


def matmul_grid(m: int, n: int, k: int, *, bm: int, bn: int, bk: int,
                order: str):
    """Grid + BlockSpec index_maps for a given loop order.

    Shared by :func:`block_matmul` and the static checker
    (:mod:`repro.analysis.kerncheck`), which evaluates the maps on
    concrete grid indices.  Returns ``(grid, amap, bmap, cmap, axis)``.
    """
    if sorted(order) != ["k", "m", "n"]:
        raise KernelShapeError(f"order {order!r} must permute 'mnk'")
    if k <= 0 or m % bm or n % bn or k % bk:
        raise KernelShapeError(
            f"tiles ({bm},{bn},{bk}) must divide dims ({m},{n},{k}) "
            f"(ops.matmul pads)")
    trip = {"m": m // bm, "n": n // bn, "k": k // bk}
    grid = tuple(trip[d] for d in order)
    axis = {d: i for i, d in enumerate(order)}

    def amap(*ids):
        return (ids[axis["m"]], ids[axis["k"]])

    def bmap(*ids):
        return (ids[axis["k"]], ids[axis["n"]])

    def cmap(*ids):
        return (ids[axis["m"]], ids[axis["n"]])

    return grid, amap, bmap, cmap, axis


def _mm_kernel_osta(a_ref, b_ref, o_ref, acc_ref, *, k_axis: int,
                    k_tiles: int):
    """Output-stationary (k innermost): f32 VMEM accumulator, flushed when
    the k sweep of this C block completes."""
    kk = pl.program_id(k_axis)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(kk == k_tiles - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_kernel_rmw(a_ref, b_ref, o_ref, *, k_axis: int, k_tiles: int):
    """k not innermost: the C block leaves VMEM while partial, so partial
    sums are read-modified-written through the output ref — exactly the
    extra W/I_slice traffic the planner charges such orders for."""
    kk = pl.program_id(k_axis)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=jnp.float32
                          ).astype(o_ref.dtype)


def block_matmul(a: jax.Array, b: jax.Array, *,
                 bm: int = 128, bn: int = 128, bk: int = 128,
                 order: str = "mnk",
                 interpret: bool | None = None) -> jax.Array:
    """C = A @ B with planner-chosen tiles and loop order.

    ``order`` is outer->inner over the grid axes, e.g. "mnk" iterates k
    fastest (output-stationary).  Dims must divide by the tiles
    (``ops.matmul`` pads).
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise KernelShapeError(f"A has k={k} but B has k={k2}")
    grid, amap, bmap, cmap, axis = matmul_grid(
        m, n, k, bm=bm, bn=bn, bk=bk, order=order)
    k_t = k // bk
    dim_sem = tuple("arbitrary" if d == "k" else "parallel" for d in order)
    k_inner = order[2] == "k"
    if k_inner:
        kernel = functools.partial(_mm_kernel_osta, k_axis=axis["k"],
                                   k_tiles=k_t)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
        out_dtype = a.dtype
    else:
        kernel = functools.partial(_mm_kernel_rmw, k_axis=axis["k"],
                                   k_tiles=k_t)
        scratch = []
        out_dtype = jnp.float32     # RMW partials accumulate in f32
    interpret = resolve_interpret(interpret)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), amap),
                  pl.BlockSpec((bk, bn), bmap)],
        out_specs=pl.BlockSpec((bm, bn), cmap),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=dim_sem)
        if not interpret else None,
        interpret=interpret,
    )(a, b)
    return out.astype(a.dtype)
