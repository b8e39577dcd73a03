"""Static kernel contract checker: the Pallas kernels vs their plans.

``repro.analysis.verifier`` proves emitted *plans* legal; this module
closes the remaining gap in the paper's "predictable offloading" claim:
that the **kernel** a plan is mapped onto (``kernels.emit``) provably
incurs exactly the traffic the plan priced.  Nothing is executed — the
checker walks the kernel's grid symbolically, evaluating BlockSpec
index_maps and ``make_async_copy`` source slices on every concrete grid
index (the same shared geometry helpers the kernel traces with), and
compares the resulting access sets against the plan's Def-3 step
sequence.

Rules (all ERROR severity — any finding means the kernel does not
implement the plan):

    rule                what it proves
    ------------------  -------------------------------------------------
    kern/emit           the layer maps onto an implemented kernel at all
    kern/step-islice    step k's DMA'd HBM region == the plan's I_slice_k
    kern/residency      step k's resident window == M_k.inp (eager-free)
    kern/write-back     output blocks == the plan's groups, each output
                        written exactly once (write-once coverage)
    kern/traffic        total elements DMA'd == what the plan charges to
                        t_l (I_slices x C_in + Λ) — traffic conservation
    kern/vmem           kernel VMEM occupancy (window + delta buffers +
                        Λ + double-buffered output blocks) <= the budget
                        the plan was solved under
    kern/hazard         the DMA pipeline's happens-before trace is free
                        of RAW/WAR/WAW races, lost-wait deadlocks and
                        leaked (never-retired) transfers
    kern/coverage       standalone kernels (block_matmul, flash_decode):
                        streamed blocks tile their operand disjointly,
                        resident blocks are truly resident, every output
                        tile is written back exactly once

Run ``python -m repro.analysis.kerncheck`` (CI lint job; exit 1 on
findings): plans every registered network with the emitable solver at a
2x-Λ VMEM budget and proves every conv layer contract-equivalent, then
statically checks the standalone GeMM/decode kernels.  The check
functions take the extracted :class:`KernelTrace` as *data*, so tests
seed mutations (shifted index_map, dropped wait, double write) into a
trace and assert the precise rule fires.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Sequence

from repro.analysis import access
from repro.analysis.diagnostics import (
    Diagnostic, Severity, VerificationReport)
from repro.configs.networks import NETWORKS
from repro.core.conv_spec import ConvSpec
from repro.core.cost_model import HardwareModel
from repro.core.strategies import GroupedStrategy
from repro.kernels.block_matmul import matmul_grid
from repro.kernels.conv2d_offload import (
    CASE_COL, CASE_FULL, CASE_ROW, eff_tile, grid_sequence, moving_right,
    step_case, t_in_cols)
from repro.kernels.emit import (
    EmittedConv, KernelEmitError, emit_layer_kernel, kernel_vmem_elements,
    plan_emitable_network)
from repro.kernels.flash_decode import decode_specs

# Big enough that nb_patches_max_S1 (Sec 4.2) admits 16-patch groups on
# the deepest registered layer (64ch 3x3 -> 64ch: 36864 MACs/patch); the
# memory budget, not compute, is what kerncheck stresses.
_DEFAULT_NBOP = 1 << 20
_DEFAULT_BUDGET_FACTOR = 2.0


# --------------------------------------------------------------------- #
# Trace extraction (symbolic grid walk — no kernel execution)
# --------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class StepTrace:
    """The access sets of one grid step of a conv offload kernel."""

    index: int
    x_load: access.Region               # HBM input region DMA'd for this step
    lam_elements: int                   # kernel elements fetched (Λ at step 0)
    window: access.Region               # resident VMEM window the step reads
    out: access.Region                  # output block written back


@dataclasses.dataclass
class KernelTrace:
    """Everything the checker extracts from one kernel instantiation."""

    name: str
    spec: ConvSpec
    t_run: int
    order: str
    vmem_elements: int
    steps: list[StepTrace]
    events: list[access.Event]


def build_conv_trace(emitted: EmittedConv) -> KernelTrace:
    """Symbolically walk ``conv2d_offload_planned``'s grid.

    Mirrors the kernel's ``pl.when`` structure exactly: per step, the
    retire-wait for the delta prefetched one step earlier, the window
    shift/splice (its static right- or left-moving branch), the next
    step's prefetch start, then the per-tap compute reads and the
    output-block write.  Every region comes from evaluating the same
    geometry helpers the kernel traces with, on concrete indices.
    Regions are logical ``(c, h, w)`` boxes; the kernel stores the same
    boxes as ``(h, w, pixel)`` buffers, a pixel's channels padded to
    lane tiles (``conv2d_offload.pixel_shape``).
    """
    spec, t = emitted.spec, emitted.t_run
    return _conv_trace(spec, t, emitted.order,
                       name=f"conv2d_offload_planned[L{emitted.layer_index}]",
                       vmem_elements=emitted.vmem_elements)


def _conv_trace(spec: ConvSpec, t: int, order: str, *, name: str,
                vmem_elements: int) -> KernelTrace:
    c, hk, wk = spec.c_in, spec.h_k, spec.w_k
    sh, sw = spec.s_h, spec.s_w
    tiles = spec.w_out // t
    t_in = t_in_cols(t, sw, wk)
    nw = t * sw
    ov_w = t_in - nw
    keep = hk - sh
    geom = dict(t_run=t, s_h=sh, s_w=sw, h_k=hk, w_k=wk,
                w_out_tiles=tiles, order=order)
    seq = grid_sequence(spec.h_out, tiles)

    def x_box(r0, rn, c0, cn):
        return access.box_region("x", (0, c), (r0, r0 + rn), (c0, c0 + cn))

    def win_box(r0=0, rn=None, c0=0, cn=None):
        return access.box_region(
            "win", (0, c), (r0, r0 + (hk if rn is None else rn)),
            (c0, c0 + (t_in if cn is None else cn)))

    def delta(case, i, jt_eff):
        """The I_slice region of a step, by its fetch case."""
        h0, w0 = i * sh, jt_eff * nw
        if case == CASE_FULL:
            return x_box(h0, hk, w0, t_in)
        if case == CASE_ROW:
            return x_box(h0 + keep, sh, w0, t_in)
        off = ov_w if moving_right(i, order == "zigzag") else 0
        return x_box(h0, hk, w0 + off, nw)

    steps: list[StepTrace] = []
    events: list[access.Event] = []
    row_full = access.box_region("row_buf", (0, c),
                                 (0, max(1, min(sh, hk))), (0, t_in))
    col_full = access.box_region("col_buf", (0, c), (0, hk), (0, nw))
    for k, (i, jt_raw) in enumerate(seq):
        jt = eff_tile(i, jt_raw, tiles, order == "zigzag")
        case = step_case(i, jt_raw, **geom)
        h0, w0 = i * sh, jt * nw
        load = delta(case, i, jt)

        if case == CASE_FULL:
            events.append(access.DmaStart("full", load, win_box(), k,
                                          tag="win full"))
            events.append(access.DmaWait("full", k))
        elif case == CASE_ROW:
            events.append(access.DmaWait("row", k))
            events.append(access.BufRead(win_box(r0=sh, rn=keep), k))
            events.append(access.BufWrite(win_box(r0=0, rn=keep), k))
            events.append(access.BufRead(row_full, k))
            events.append(access.BufWrite(win_box(r0=keep, rn=sh), k))
        elif moving_right(i, order == "zigzag"):        # CASE_COL, right
            events.append(access.DmaWait("col", k))
            events.append(access.BufRead(win_box(c0=nw, cn=ov_w), k))
            events.append(access.BufWrite(win_box(c0=0, cn=ov_w), k))
            events.append(access.BufRead(col_full, k))
            events.append(access.BufWrite(win_box(c0=ov_w, cn=nw), k))
        else:                                           # CASE_COL, left
            events.append(access.DmaWait("col", k))
            events.append(access.BufRead(win_box(c0=0, cn=ov_w), k))
            events.append(access.BufWrite(win_box(c0=nw, cn=ov_w), k))
            events.append(access.BufRead(col_full, k))
            events.append(access.BufWrite(win_box(c0=0, cn=nw), k))

        if k + 1 < len(seq):                            # prefetch next delta
            i_n, jt_raw_n = seq[k + 1]
            jt_n = eff_tile(i_n, jt_raw_n, tiles, order == "zigzag")
            case_n = step_case(i_n, jt_raw_n, **geom)
            if case_n == CASE_ROW:
                events.append(access.DmaStart(
                    "row", delta(case_n, i_n, jt_n), row_full, k,
                    tag="row prefetch"))
            elif case_n == CASE_COL:
                events.append(access.DmaStart(
                    "col", delta(case_n, i_n, jt_n), col_full, k,
                    tag="col prefetch"))

        out = access.box_region("out", (0, spec.c_out), (i, i + 1),
                                (jt * t, jt * t + t))
        events.append(access.BufRead(win_box(), k))     # per-tap dots
        events.append(access.BufWrite(out, k))
        steps.append(StepTrace(
            index=k, x_load=load,
            lam_elements=spec.kernel_elements if k == 0 else 0,
            window=x_box(h0, hk, w0, t_in), out=out))
    return KernelTrace(name=name, spec=spec, t_run=t, order=order,
                       vmem_elements=vmem_elements, steps=steps,
                       events=events)


# --------------------------------------------------------------------- #
# Contract rules (pure functions of the trace — tests mutate the trace)
# --------------------------------------------------------------------- #

def _box_pixmask(spec: ConvSpec, region: access.Region) -> int:
    """Spatial-pixel bitmask of an input-region box (channel axis
    dropped — the plan ledger is in spatial units)."""
    (_, _), (r0, r1), (c0, c1) = region.box
    m = 0
    for h in range(r0, min(r1, spec.h_in)):
        m |= ((1 << (c1 - c0)) - 1) << (h * spec.w_in + c0)
    return m


def _out_patchmask(spec: ConvSpec, region: access.Region) -> int:
    """Patch bitmask of an output-block box."""
    (_, _), (r0, r1), (c0, c1) = region.box
    m = 0
    for i in range(r0, r1):
        for j in range(c0, c1):
            m |= 1 << spec.patch_id(i, j)
    return m


def check_conv_trace(trace: KernelTrace, strategy: GroupedStrategy,
                     budget: int | None, *,
                     layer: int | None = None) -> list[Diagnostic]:
    """All contract rules for one conv kernel trace vs its plan."""
    spec = trace.spec
    diags: list[Diagnostic] = []

    def err(rule: str, msg: str, *, step: int | None = None,
            **data) -> None:
        diags.append(Diagnostic.make(rule, Severity.ERROR, msg,
                                     layer=layer, step=step, **data))

    plan_steps = strategy.to_steps()[:-1]       # drop the terminal flush
    if len(trace.steps) != len(plan_steps):
        err("kern/step-islice",
            f"kernel has {len(trace.steps)} grid steps but the plan has "
            f"{len(plan_steps)} compute steps",
            kernel_steps=len(trace.steps), plan_steps=len(plan_steps))
        return diags

    total_loaded = 0
    write_counts: dict[int, int] = {}
    for st, ps in zip(trace.steps, plan_steps):
        got = _box_pixmask(spec, st.x_load)
        want = ps.i_slice
        if got != want:
            err("kern/step-islice",
                f"DMA'd region {st.x_load.describe()} != plan I_slice "
                f"({bin(got ^ want).count('1')} pixels differ)",
                step=st.index, dma_pixels=got.bit_count(),
                islice_pixels=want.bit_count())
        need = spec.group_mask(ps.group)
        win = _box_pixmask(spec, st.window)
        if win != need:
            err("kern/residency",
                f"resident window {st.window.describe()} != M_k.inp "
                f"(plan holds {need.bit_count()} pixels, kernel "
                f"{win.bit_count()})", step=st.index)
        out_got = _out_patchmask(spec, st.out)
        if out_got != ps.out:
            err("kern/write-back",
                f"output block {st.out.describe()} != plan group "
                f"(block covers {out_got.bit_count()} patches, group has "
                f"{ps.out.bit_count()})", step=st.index)
        for pid in spec.pixels_of_mask(out_got):
            write_counts[pid] = write_counts.get(pid, 0) + 1
        total_loaded += st.x_load.elements + st.lam_elements

    bad = {p: n for p, n in write_counts.items() if n != 1}
    missing = spec.num_patches - len(write_counts)
    if bad or missing:
        err("kern/write-back",
            f"output not covered write-once: {missing} patches never "
            f"written, {len(bad)} written more than once",
            missing=missing, multi=len(bad))

    want_traffic = (strategy.pixels_loaded() * spec.c_in
                    + spec.kernel_elements)
    if total_loaded != want_traffic:
        err("kern/traffic",
            f"kernel DMAs {total_loaded} elements but the plan charges "
            f"{want_traffic} to t_l — predicted duration would lie",
            loaded=total_loaded, charged=want_traffic)

    if budget is not None and trace.vmem_elements > budget:
        err("kern/vmem",
            f"kernel occupies {trace.vmem_elements} VMEM elements; the "
            f"plan was solved under size_mem={budget}",
            occupancy=trace.vmem_elements, budget=budget)

    for hz in access.hazard_scan(trace.events):
        err("kern/hazard", hz.describe(), step=hz.step, kind=hz.kind)
    return diags


# --------------------------------------------------------------------- #
# Standalone kernels: BlockSpec walks for GeMM / decode attention
# --------------------------------------------------------------------- #

def _lex_indices(grid: tuple[int, ...]):
    """Grid indices in Pallas execution order (last axis fastest)."""
    idx = [0] * len(grid)
    while True:
        yield tuple(idx)
        for ax in reversed(range(len(grid))):
            idx[ax] += 1
            if idx[ax] < grid[ax]:
                break
            idx[ax] = 0
        else:
            return


def check_block_matmul(m: int, n: int, k: int, *, bm: int, bn: int,
                       bk: int, order: str) -> list[Diagnostic]:
    """Static checks of ``block_matmul``'s BlockSpec schedule.

    Proves: A/B blocks stay in bounds; for the output-stationary order
    (k innermost) every C tile's visits are one contiguous run — the
    block is written back exactly once when it leaves VMEM; every C tile
    is visited (coverage); revisit counts match the planner's model (the
    k sweep revisits the C tile k_t times)."""
    diags: list[Diagnostic] = []
    grid, amap, bmap, cmap, _ = matmul_grid(m, n, k, bm=bm, bn=bn, bk=bk,
                                            order=order)

    def err(msg: str, *, step: int | None = None, **data) -> None:
        diags.append(Diagnostic.make("kern/coverage", Severity.ERROR, msg,
                                     step=step, **data))

    visits: dict[tuple[int, int], list[int]] = {}
    for step, ids in enumerate(_lex_indices(grid)):
        ai, ak = amap(*ids)
        bkk, bj = bmap(*ids)
        if not (0 <= ai * bm < m and 0 <= ak * bk < k):
            err(f"A block ({ai},{ak}) out of bounds", step=step)
        if not (0 <= bkk * bk < k and 0 <= bj * bn < n):
            err(f"B block ({bkk},{bj}) out of bounds", step=step)
        if ak != bkk:
            err(f"A reads k-tile {ak} but B reads {bkk} — the dot "
                f"contracts mismatched tiles", step=step)
        visits.setdefault(cmap(*ids), []).append(step)

    want_tiles = (m // bm) * (n // bn)
    if len(visits) != want_tiles:
        err(f"C coverage: {len(visits)} tiles visited, grid has "
            f"{want_tiles}", visited=len(visits), tiles=want_tiles)
    k_t = k // bk
    for tile, ss in visits.items():
        if len(ss) != k_t:
            err(f"C tile {tile} visited {len(ss)} times, k sweep "
                f"needs {k_t}")
        if ss != list(range(ss[0], ss[0] + len(ss))) and order[2] == "k":
            err(f"C tile {tile} leaves VMEM and returns (visits {ss}) — "
                f"the output-stationary kernel would write it back "
                f"twice")
    return diags


def check_decode(g: int, d: int, s: int, *, bkv: int) -> list[Diagnostic]:
    """Static checks of ``decode_attention``'s schedule: q and the output
    block resident (constant index_map), K/V blocks a disjoint exact
    cover of the cache."""
    diags: list[Diagnostic] = []
    grid, qmap, kvmap, omap = decode_specs(g, d, s, bkv)
    seen: set[int] = set()
    for i in range(grid[0]):
        if qmap(i) != (0, 0) or omap(i) != (0, 0):
            diags.append(Diagnostic.make(
                "kern/coverage", Severity.ERROR,
                f"q/output block moves at step {i} — the accumulator "
                f"state would be lost", step=i))
        row, col = kvmap(i)
        if col != 0 or row in seen or not 0 <= row * bkv < s:
            diags.append(Diagnostic.make(
                "kern/coverage", Severity.ERROR,
                f"KV block ({row},{col}) repeats or out of bounds",
                step=i))
        seen.add(row)
    if len(seen) * bkv != s:
        diags.append(Diagnostic.make(
            "kern/coverage", Severity.ERROR,
            f"KV blocks cover {len(seen) * bkv} of {s} cache positions"))
    return diags


# --------------------------------------------------------------------- #
# Whole-repo entry points (tests + CI)
# --------------------------------------------------------------------- #

def network_budget(specs: Sequence[ConvSpec],
                   factor: float = _DEFAULT_BUDGET_FACTOR) -> HardwareModel:
    """The budget kerncheck plans under: ``factor`` x the largest Λ."""
    lam = max(s.kernel_elements for s in specs)
    return HardwareModel(nbop_pe=_DEFAULT_NBOP,
                         size_mem=int(factor * lam))


def check_network(name: str, specs: Sequence[ConvSpec] | None = None, *,
                  hw: HardwareModel | None = None) -> VerificationReport:
    """Plan one network with the emitable solver and prove every conv
    layer's emitted kernel contract-equivalent to its LayerPlan."""
    specs = list(NETWORKS[name] if specs is None else specs)
    hw = hw or network_budget(specs)
    report = VerificationReport(subject=f"kerncheck {name}")
    plan = plan_emitable_network(specs, hw, name=name)
    for lp in plan.layers:
        try:
            emitted = emit_layer_kernel(lp)
        except KernelEmitError as e:
            report.add(Diagnostic.make(
                "kern/emit", Severity.ERROR, str(e), layer=lp.index))
            continue
        trace = build_conv_trace(emitted)
        report.extend(check_conv_trace(trace, lp.strategy, hw.size_mem,
                                       layer=lp.index))
        report.checked_layers += 1
        report.checked_steps += len(trace.steps)
    return report


_STANDALONE_GEMM = [
    dict(m=256, n=384, k=512, bm=128, bn=128, bk=128, order="mnk"),
    dict(m=256, n=256, k=256, bm=128, bn=128, bk=128, order="nmk"),
    dict(m=256, n=256, k=512, bm=128, bn=128, bk=128, order="kmn"),
    dict(m=384, n=256, k=256, bm=128, bn=128, bk=128, order="mkn"),
]
_STANDALONE_DECODE = [
    dict(g=8, d=64, s=2048, bkv=512),
    dict(g=4, d=128, s=4096, bkv=1024),
]


def run_all(networks: Sequence[str] | None = None) -> VerificationReport:
    """The CI entry: every registered network + the standalone kernels."""
    merged = VerificationReport(subject="kerncheck")
    for name in (networks or sorted(NETWORKS)):
        rep = check_network(name)
        merged.extend(rep.diagnostics)
        merged.checked_layers += rep.checked_layers
        merged.checked_steps += rep.checked_steps
    for cfg in _STANDALONE_GEMM:
        merged.extend(check_block_matmul(
            cfg["m"], cfg["n"], cfg["k"], bm=cfg["bm"], bn=cfg["bn"],
            bk=cfg["bk"], order=cfg["order"]))
    for cfg in _STANDALONE_DECODE:
        merged.extend(check_decode(cfg["g"], cfg["d"], cfg["s"],
                                   bkv=cfg["bkv"]))
    return merged


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.kerncheck",
        description="Prove the Pallas kernels implement their plans "
                    "(static access-set + hazard analysis).")
    ap.add_argument("--network", action="append", dest="networks",
                    choices=sorted(NETWORKS),
                    help="check only this network (repeatable)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full report as JSON")
    args = ap.parse_args(argv)
    report = run_all(args.networks)
    if args.json:
        print(report.to_json_str())
    else:
        print(report.render())
    return 0 if report.ok else 1


if __name__ == "__main__":                      # pragma: no cover
    sys.exit(main())
