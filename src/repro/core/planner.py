"""Offloading-schedule planner: the paper's formalism applied to TPU tiling.

This is the beyond-paper generalization described in DESIGN.md §2/§4.  The
paper's strategy model — steps that (free, write-back, load I_slice/K_sub,
compute) against an on-chip memory of size ``size_MEM`` with a PE of
``nbop_PE`` — maps onto Pallas kernels as:

    on-chip memory  = VMEM budget
    a step          = one grid iteration
    I_slice/K_sub   = BlockSpec-driven (or explicit-DMA) HBM->VMEM fetches
    kept-for-later  = block revisiting (index_map unchanged between steps)
    delta (eq. 15)  = HBM bytes moved / bandwidth + step overheads

For the block GeMM and flash decode the planner enumerates candidate
*rectangular* strategies (tile shapes x loop orders), prices each with the
paper's duration model, and returns the argmin.  Both the paper-faithful
additive duration (no compute/copy overlap) and the overlapped duration
(max of roofline terms — what a double-buffered TPU kernel achieves) are
reported; optimisation uses the overlapped one by default.  The
convolution is not planned here: ``kernels.emit.grid_solve`` picks its
run length, in Def-3 cycles under the plan's ``size_mem``, through
``core.network_planner.plan_network``.
"""
from __future__ import annotations

import dataclasses
import itertools

from repro.core.cost_model import TPU_V5E, TpuChipModel


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _round_up(a: int, m: int) -> int:
    return _ceil_div(a, m) * m


@dataclasses.dataclass(frozen=True)
class Plan:
    """A chosen offloading schedule for one operator instance."""

    kind: str
    tiles: dict
    order: str
    steps: int
    hbm_bytes: int              # sum of I_slice/K_sub/W over all steps
    flops: int
    vmem_bytes: int             # peak on-chip footprint (eq. 12 analogue)
    duration_additive: float    # paper Def 3: loads + writes + compute
    duration_overlapped: float  # max(mem, compute) — double-buffered kernel

    @property
    def arithmetic_intensity(self) -> float:
        return self.flops / max(1, self.hbm_bytes)


# --------------------------------------------------------------------- #
# Block GeMM (paper Sec 1.3: TMMA/VTA adaptation — "we need to slightly
# adapt our ILP problem").  Strategies = loop orders x tile shapes.
# --------------------------------------------------------------------- #

_ORDERS = ("mnk", "mkn", "nmk", "nkm", "kmn", "knm")   # outer->inner


def _gemm_bytes(m_t: int, n_t: int, k_t: int, bm: int, bn: int, bk: int,
                mm: int, nn: int, kk: int, order: str,
                dtype_bytes: int, acc_bytes: int) -> int:
    """HBM bytes for C[M,N] += A[M,K] B[K,N] under a loop order, counting
    Pallas revisiting: a block is re-fetched only when its index changes
    between consecutive steps (the formalism's I_slice).

    A blocks are indexed by (m,k), B by (k,n), C by (m,n).  For an operand
    whose indices are all *outside* the innermost varying loops, consecutive
    steps revisit the same block -> loaded once per distinct index tuple of
    the loops it depends on, in loop order."""
    inner = order[2]            # fastest-varying grid dim
    a_bytes = bm * bk * dtype_bytes
    b_bytes = bk * bn * dtype_bytes
    c_bytes = bm * bn * dtype_bytes

    def loads(dep: set[str]) -> int:
        """Distinct consecutive index changes for an operand depending on
        ``dep`` ⊆ {m,n,k}: product of trip counts of all loops at or outside
        the innermost loop the operand depends on."""
        trips = {"m": m_t, "n": n_t, "k": k_t}
        # position of the innermost loop this operand depends on:
        deepest = max(order.index(d) for d in dep)
        total = 1
        for pos in range(deepest + 1):
            total *= trips[order[pos]]
        return total

    total = loads({"m", "k"}) * a_bytes + loads({"k", "n"}) * b_bytes
    if order.index("k") < 2:
        # k is not innermost -> C block leaves/re-enters VMEM while partial:
        # read-modify-write per visit (except first read / last write).
        visits = loads({"m", "n"})
        total += (2 * visits - 2 * m_t * n_t) * c_bytes + \
            m_t * n_t * c_bytes          # final writes
    else:
        # output-stationary: C written once per (m,n)
        total += m_t * n_t * c_bytes
    return total


def plan_matmul(m: int, n: int, k: int, dtype_bytes: int = 2,
                chip: TpuChipModel = TPU_V5E,
                vmem_fraction: float = 0.7) -> Plan:
    """Choose (bm, bn, bk, loop order) minimising the paper's duration."""
    budget = int(chip.vmem_bytes * vmem_fraction)
    flops = 2 * m * n * k
    cands: list[Plan] = []
    sizes = [128, 256, 512, 1024, 2048]
    for bm, bn, bk in itertools.product(sizes, repeat=3):
        bm_, bn_, bk_ = min(bm, _round_up(m, 8)), min(bn, _round_up(n, 128)), \
            min(bk, _round_up(k, 128))
        # VMEM: A + B blocks (dtype) + C accumulator (f32), double-buffered
        vmem = (2 * (bm_ * bk_ + bk_ * bn_) * dtype_bytes
                + bm_ * bn_ * 4)
        if vmem > budget:
            continue
        m_t, n_t, k_t = _ceil_div(m, bm_), _ceil_div(n, bn_), _ceil_div(k, bk_)
        for order in _ORDERS:
            hbm = _gemm_bytes(m_t, n_t, k_t, bm_, bn_, bk_, m, n, k,
                              order, dtype_bytes, dtype_bytes)
            t_mem = hbm / chip.hbm_bw
            t_cmp = flops / chip.peak_flops
            cands.append(Plan(
                kind="matmul", tiles={"bm": bm_, "bn": bn_, "bk": bk_},
                order=order, steps=m_t * n_t * k_t, hbm_bytes=hbm,
                flops=flops, vmem_bytes=vmem,
                duration_additive=t_mem + t_cmp,
                duration_overlapped=max(t_mem, t_cmp)))
    if not cands:
        raise ValueError("no tile fits VMEM")
    return min(cands, key=lambda p: (p.duration_overlapped,
                                     p.duration_additive, p.steps))


# --------------------------------------------------------------------- #
# Decode attention: S1 with roles swapped — Q is the resident "kernel set",
# KV blocks are the patches (disjoint, stride == block -> no halo).
# --------------------------------------------------------------------- #

def plan_decode_attention(seq_len: int, head_dim: int, q_rows: int,
                          dtype_bytes: int = 2,
                          chip: TpuChipModel = TPU_V5E,
                          vmem_fraction: float = 0.7) -> Plan:
    budget = int(chip.vmem_bytes * vmem_fraction)
    flops = 4 * q_rows * seq_len * head_dim      # QK^T + PV
    best: Plan | None = None
    bkv = 128
    while bkv <= max(128, min(seq_len, 8192)):
        # resident: q, acc, m, l; streamed: K,V double-buffered
        vmem = (q_rows * head_dim * dtype_bytes
                + q_rows * head_dim * 4 + 2 * q_rows * 4
                + 2 * 2 * bkv * head_dim * dtype_bytes)
        if vmem <= budget and seq_len % bkv == 0:
            steps = seq_len // bkv
            hbm = 2 * seq_len * head_dim * dtype_bytes \
                + 2 * q_rows * head_dim * dtype_bytes
            t_mem = hbm / chip.hbm_bw
            t_cmp = flops / chip.peak_flops
            cand = Plan(kind="decode_attention", tiles={"bkv": bkv},
                        order="kv", steps=steps, hbm_bytes=hbm, flops=flops,
                        vmem_bytes=vmem,
                        duration_additive=t_mem + t_cmp,
                        duration_overlapped=max(t_mem, t_cmp))
            # bytes are block-size independent here; prefer fewer steps
            # (lower per-step overhead = fewer t_acc terms in paper units)
            if best is None or cand.steps < best.steps:
                best = cand
        bkv *= 2
    if best is None:
        raise ValueError("no KV block fits VMEM")
    return best
