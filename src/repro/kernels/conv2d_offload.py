"""Pallas TPU kernels: S1 convolution offloading (paper Sec 4 on TPU).

Strategy S1, faithfully mapped to the TPU memory hierarchy:

  * **K_sub / kernel residency** — all kernels Λ are fetched once and stay
    in VMEM for the whole sweep.  Expressed with a BlockSpec whose index_map
    is constant, so Pallas revisits (never re-fetches) the block: exactly
    "loaded during the first step and never freed until the last step"
    (Def 16).
  * **I_slice** — the input lives in HBM (the paper's DRAM).  Each grid
    step DMAs its window (or the part of it not yet resident) into a VMEM
    scratch buffer with ``pltpu.make_async_copy`` — action a4.
  * **patch groups** — one step computes a row-run of T output columns for
    *all* C_out channels (Property 1).  T comes from the planner,
    ``kernels.emit.grid_solve`` (the nb_patches_max analogue under the
    VMEM budget).  Grid order is zigzag (paper Sec 7.2) or row-by-row.
  * **W / write-back** — the step's output block leaves VMEM when the grid
    moves on — action a3.

The geometry helpers below are shared with ``repro.analysis.kerncheck``,
which evaluates them on concrete grid indices to derive the kernel's
static access trace.

:func:`conv2d_offload_planned` is the kernel ``kernels.emit`` maps
``LayerPlan``s onto, on (H, W, C) arrays with channels on the 128-lane
axis: the window stays resident in VMEM and each step DMAs only its
**I_slice delta** (new columns within a row, new rows at a zigzag row
turn), *prefetched* one step ahead into a separate delta buffer so the
copy overlaps the previous step's MXU work, then accumulates one
(T, C_in) x (C_in, C_out) dot per kernel tap, or per group of taps where
a pixel's channels fill no more than half of its 128 lanes.
Double-buffering is exactly the part that is easy to get subtly wrong (a
dropped wait, a prefetch aimed at the live window), which is why
``kerncheck`` proves its DMA trace hazard-free and its per-step regions
equal to the plan's I_slices before the kernel is trusted.  It compiles
for the TPU (``tests/test_tpu_compile.py``); off the TPU it runs in
interpret mode (``kernels.resolve_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import KernelShapeError, resolve_interpret

# Step cases of the planned kernel (shared with the static checker).
CASE_FULL = "full"          # DMA the whole window (first step / no overlap)
CASE_ROW = "row-delta"      # zigzag row turn: fetch the s_h new rows
CASE_COL = "col-delta"      # within-row move: fetch the t_run*s_w new cols

# Semaphore slots of the planned kernel's DMA semaphore array.
SEM_FULL, SEM_ROW, SEM_COL = 0, 1, 2


# --------------------------------------------------------------------- #
# Shared grid geometry (evaluated on tracers in-kernel, on ints by the
# static checker — keep everything branch-free arithmetic over i/jt).
# --------------------------------------------------------------------- #

def t_in_cols(t_run: int, s_w: int, w_k: int) -> int:
    """Input columns covered by a ``t_run``-patch row-run."""
    return (t_run - 1) * s_w + w_k


def eff_tile(i, jt, w_out_tiles: int, zigzag: bool):
    """Physical column-tile index of grid step ``(i, jt)``.

    Zigzag reverses odd rows; the arithmetic form works for both Python
    ints (checker) and traced values (kernel)."""
    if not zigzag:
        return jt
    return jt + (i % 2) * (w_out_tiles - 1 - 2 * jt)


def moving_right(i, zigzag: bool):
    """Whether within-row steps of row ``i`` advance left-to-right."""
    if not zigzag:
        return True
    return i % 2 == 0


def grid_sequence(h_out: int, w_out_tiles: int):
    """The Pallas grid's sequential step order: last axis fastest."""
    return [(i, jt) for i in range(h_out) for jt in range(w_out_tiles)]


def step_case(i: int, jt: int, *, t_run: int, s_h: int, s_w: int,
              h_k: int, w_k: int, w_out_tiles: int, order: str) -> str:
    """Which I_slice the planned kernel fetches at grid step ``(i, jt)``.

    Concrete-index form of the kernel's ``pl.when`` structure: the first
    step and any step whose window is disjoint from its predecessor's
    fetch the full window; a zigzag row turn (same column window, one
    stride down) fetches only the new rows; a within-row move fetches
    only the new columns.  Row order with more than one column tile jumps
    back to the row's left edge at each turn — a (mostly) disjoint
    window, fetched in full."""
    zig = order == "zigzag"
    if i == 0 and jt == 0:
        return CASE_FULL
    if jt == 0:                                   # row turn
        if (zig or w_out_tiles == 1) and h_k > s_h:
            return CASE_ROW
        return CASE_FULL
    if t_in_cols(t_run, s_w, w_k) > t_run * s_w:  # windows share columns
        return CASE_COL
    return CASE_FULL


def _conv_geometry(x_shape: tuple[int, ...], w_shape: tuple[int, ...],
                   t_run: int, s_h: int, s_w: int
                   ) -> tuple[int, int]:
    """Validate (H_in, W_in, C_in) input and (H_K, W_K, C_in, N) kernel
    shapes; return (h_out, w_out_tiles)."""
    h_in, w_in, c_in = x_shape
    h_k, w_k, c_in2, _ = w_shape
    if c_in != c_in2:
        raise KernelShapeError(
            f"input has {c_in} channels but kernels expect {c_in2}")
    h_out = (h_in - h_k) // s_h + 1
    w_out = (w_in - w_k) // s_w + 1
    if h_out <= 0 or w_out <= 0:
        raise KernelShapeError(
            f"kernel {h_k}x{w_k} does not fit input {h_in}x{w_in}")
    if t_run <= 0 or w_out % t_run != 0:
        raise KernelShapeError(
            f"t_run={t_run} must divide w_out={w_out} "
            f"(kernels.emit.grid_solve picks one that does)")
    return h_out, w_out // t_run


# --------------------------------------------------------------------- #
# Planned kernel: resident window + prefetched I_slice deltas
# --------------------------------------------------------------------- #
#
# Layout: channels on the 128-lane axis.  ``pixel_shape`` stores a
# pixel's channels as whole 128-lane rows of 32-bit words: C_in padded to
# a multiple of 128 lanes in f32, and to two rows of lanes in bf16 (a
# packed bf16 tile pairs two rows; with one row per pixel it would pair
# neighbouring pixels, and a window could not start at an odd column).
# Mosaic cuts an HBM array at any offset only along dims whose minor dim
# is one 128-lane tile, so an f32 pixel of ``ph = L / 128`` lane tiles
# lies as ``ph`` consecutive rows of 128 lanes: the input is (H, W * ph,
# 128) in HBM, pixel column w taking rows [w * ph, (w + 1) * ph), and
# every column offset and count below is scaled by ``ph`` (1 for up to
# 128 channels, where the layout is (H, W, 128)).  bf16 keeps (H, W,
# *pixel) with ``ph`` 1.  The resident window is (h_k, t_in * ph,
# *tail), the delta buffers are (h_k, nw * ph, *tail) and (s_h, t_in *
# ph, *tail) (see ``_dma_buffer``), Λ is (h_k, w_k, *pixel, C_out) and
# each step writes one (t_run, C_out) output block.  Every VMEM slice
# below is static; only the HBM-side DMA offsets depend on the grid
# index.
#
# Narrow pixels share dots.  A one-row 32-bit pixel of ``c_in <= 64``
# channels leaves at least half its 128 lanes zero, so ``g = 128 //
# c_in`` taps fit one dot: tap j of a group is lane-rotated by ``j *
# c_in`` and the g slices are summed, which concatenates their channels
# exactly, and Λ is packed the same way, (dots, 128, C_out)
# (``tap_group``, ``pack_taps``).

def pixel_shape(c: int, dtype) -> tuple[int, ...]:
    """How the planned kernel stores one pixel's ``c`` channels: ``(L,)``
    lanes for 32-bit types, ``(2, L)`` rows x lanes for bf16; L is a
    multiple of 128.  Channel ``k`` sits at row ``k // L``, lane
    ``k % L`` (a plain reshape of the zero-padded channel axis)."""
    rows = 4 // jnp.dtype(dtype).itemsize
    lanes = -(-c // (rows * 128)) * 128
    return (lanes,) if rows == 1 else (rows, lanes)


def _to_pixels(a: jax.Array, axis: int, pix: tuple[int, ...]) -> jax.Array:
    """Zero-pad ``a``'s channel ``axis`` and split it into ``pix``."""
    size = 1
    for d in pix:
        size *= d
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, size - a.shape[axis])
    a = jnp.pad(a, widths)
    return a.reshape(a.shape[:axis] + pix + a.shape[axis + 1:])


def tap_group(c: int, dtype) -> int:
    """How many kernel taps share one MXU dot for ``c`` input channels:
    ``128 // c`` where a pixel is one row of 128 lanes of 32-bit words,
    2 or more only at ``c <= 64``; 1 (a dot per tap) for wider pixels
    and for bf16's two-row pixel."""
    return 128 // c if pixel_shape(c, dtype) == (128,) else 1


def dots_per_step(h_k: int, w_k: int, c: int, dtype) -> int:
    """MXU dots one grid step issues: one per group of ``tap_group``
    taps, else one per tap and per pixel row that holds channels."""
    g = tap_group(c, dtype)
    if g > 1:
        return -(-(h_k * w_k) // g)
    pix = pixel_shape(c, dtype)
    rows = pix[0] // 128 if len(pix) == 1 else -(-c // pix[-1])
    return h_k * w_k * rows


def pack_taps(w: jax.Array, g: int) -> jax.Array:
    """(h_k, w_k, c, n) kernels as the (dots, 128, n) Λ of tap groups:
    tap ``t = kh * w_k + kw``, channel ``k`` lies in dot ``t // g`` at
    lane ``(t % g) * c + k``; the lanes no tap uses are zero."""
    h_k, w_k, c, n = w.shape
    taps = h_k * w_k
    dots = -(-taps // g)
    w = jnp.pad(w.reshape(taps, c, n), ((0, dots * g - taps), (0, 0), (0, 0)))
    return jnp.pad(w.reshape(dots, g * c, n),
                   ((0, 0), (0, 128 - g * c), (0, 0)))


def _padded_vmem_bytes(shape: tuple[int, ...], dtype) -> int:
    """Bytes a VMEM buffer takes once its last two dims are padded to the
    (sublane, 128) tile: 8 sublanes for 32-bit types, 16 for bf16."""
    *lead, rows, lanes = shape
    itemsize = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // itemsize
    n = -(-rows // sub) * sub * (-(-lanes // 128) * 128)
    for d in lead:
        n *= d
    return n * itemsize


def _dma_buffer(rows_cols: tuple[int, int], pix: tuple[int, ...]
                ) -> tuple[int, ...]:
    """Shape of a VMEM buffer that DMAs fill with ``rows_cols`` pixels.

    With one lane row per pixel (32-bit types) the column dim is the
    tiled sublane dim, and Mosaic sizes a DMA's wait by its destination
    padded to whole sublane tiles.  So the column dim is allocated padded
    to 8 and every DMA targets the exact ``[:, :cols]`` slice, whose size
    equals the copy's."""
    rows, cols = rows_cols
    if len(pix) == 1:
        cols = -(-cols // 8) * 8
    return (rows, cols, *pix)


# Mosaic's own scratch (spilled accumulators, relayout temporaries) on
# top of the buffers the kernel declares.
_VMEM_HEADROOM_BYTES = 1 << 20


def _tap_operands(win_buf, w_ref, *, t_run: int, s_w: int, h_k: int,
                  w_k: int, rows_used: int, ph: int, g: int, c_in: int):
    """The (t_run, L) window slice and (L, C_out) Λ slice of each MXU dot
    of a step, in order, each window slice static.  With ``g > 1`` a dot
    takes g taps, tap j of the group lane-rotated by ``j * c_in`` onto
    its share of Λ's lanes (module note); otherwise one dot per tap (and
    per pixel row that holds channels)."""
    def cols(kw):
        return pl.ds(kw, t_run) if s_w == 1 else pl.ds(kw, t_run, stride=s_w)

    if g > 1:
        taps = [divmod(t, w_k) for t in range(h_k * w_k)]
        for d in range(0, len(taps), g):
            xs = None
            for j, (kh, kw) in enumerate(taps[d:d + g]):
                part = win_buf[kh, cols(kw), :]
                if j:
                    part = pltpu.roll(part, j * c_in, axis=1)
                xs = part if xs is None else xs + part
            yield xs, w_ref[d // g]
        return
    for kh in range(h_k):
        for kw in range(w_k):
            if win_buf.ndim == 3 and ph == 1:
                yield win_buf[kh, cols(kw), :], w_ref[kh, kw]
            elif win_buf.ndim == 3:
                # row r of each of the t_run pixels: every ph-th row
                yield from [(win_buf[kh, pl.ds(kw * ph + r, t_run,
                                               stride=s_w * ph), :],
                             w_ref[kh, kw, r]) for r in range(ph)]
            else:
                yield from [(win_buf[kh, cols(kw), r, :], w_ref[kh, kw, r])
                            for r in range(rows_used)]


def _tap_dots(win_buf, w_ref, o_ref, *, precision, **geometry):
    """The step's MXU dots (``_tap_operands``), accumulated in f32."""
    acc = None
    for xs, ws in _tap_operands(win_buf, w_ref, **geometry):
        part = jnp.dot(xs, ws, preferred_element_type=jnp.float32,
                       precision=precision)
        acc = part if acc is None else acc + part
    o_ref[...] = acc.astype(o_ref.dtype)


def _conv_planned_kernel(x_hbm, w_ref, o_ref, win_buf, col_buf, row_buf,
                         sems, *,
                         t_run: int, s_h: int, s_w: int, h_k: int,
                         w_k: int, h_out: int, w_out_tiles: int,
                         zigzag: bool, rows_used: int, ph: int, g: int,
                         c_in: int, precision):
    """One plan step: retire the prefetched delta, update the resident
    window, prefetch the next step's delta, then the MXU dots.
    Column offsets and counts are in rows of the (H, W * ph, ...) layout
    (module note), so a pixel column is ``ph`` of them."""
    i = pl.program_id(0)
    jt_raw = pl.program_id(1)
    tiles = w_out_tiles
    jt = eff_tile(i, jt_raw, tiles, zigzag)
    t_in_px = t_in_cols(t_run, s_w, w_k)
    nw_px = t_run * s_w                 # new columns per within-row move
    ov_px = t_in_px - nw_px             # columns shared with the neighbour
    t_in, nw, ov_w = t_in_px * ph, nw_px * ph, ov_px * ph
    keep_rows = h_k - s_h               # rows shared across a row turn
    row_delta = (zigzag or tiles == 1) and keep_rows > 0
    col_delta = ov_w > 0

    h0 = i * s_h
    w0 = jt * nw
    first = (i == 0) & (jt_raw == 0)
    rowchg = (jt_raw == 0) & (i > 0)
    within = jt_raw > 0

    full_cond = first
    if not row_delta:
        full_cond = full_cond | rowchg
    if not col_delta:
        full_cond = full_cond | within

    @pl.when(full_cond)
    def _full():
        # No usable overlap with the previous window: synchronous fetch
        # of the whole (h_k, t_in) box of pixels.
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(h0, h_k), pl.ds(w0, t_in)],
            win_buf.at[:, :t_in], sems.at[SEM_FULL])
        cp.start()
        cp.wait()

    if row_delta:
        @pl.when(rowchg)
        def _row():
            # Retire the row prefetch issued one step ago, shift the kept
            # rows up, splice the s_h new rows in at the bottom.
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(h0 + keep_rows, s_h), pl.ds(w0, t_in)],
                row_buf.at[:, :t_in], sems.at[SEM_ROW]).wait()
            win_buf[:keep_rows, :t_in] = win_buf[s_h:, :t_in]
            win_buf[keep_rows:, :t_in] = row_buf[:, :t_in]

    if col_delta:
        # One branch per sweep direction, so every window slice is static:
        # moving right keeps the window's last ov_w columns and appends
        # the delta; moving left keeps its first ov_w and prepends it.
        for right in ((True, False) if zigzag else (True,)):
            @pl.when(within & (moving_right(i, zigzag) == right))
            def _col(right=right):
                delta_off = ov_w if right else 0
                pltpu.make_async_copy(
                    x_hbm.at[pl.ds(h0, h_k), pl.ds(w0 + delta_off, nw)],
                    col_buf.at[:, :nw], sems.at[SEM_COL]).wait()
                if right:
                    win_buf[:, :ov_w] = win_buf[:, nw:t_in]
                    win_buf[:, ov_w:t_in] = col_buf[:, :nw]
                else:
                    win_buf[:, nw:t_in] = win_buf[:, :ov_w]
                    win_buf[:, :nw] = col_buf[:, :nw]

    # Prefetch the NEXT step's delta while this step computes — the
    # double-buffering whose soundness kerncheck proves (the copy writes
    # col_buf/row_buf, never the win_buf this step still reads).
    is_last = (i == h_out - 1) & (jt_raw == tiles - 1)
    nxt_turn = jt_raw == tiles - 1
    i_n = i + nxt_turn
    jt_n = eff_tile(i_n, (jt_raw + 1) * (1 - nxt_turn), tiles, zigzag)
    h0_n = i_n * s_h
    w0_n = jt_n * nw

    if row_delta:
        @pl.when((~is_last) & nxt_turn)
        def _prefetch_row():
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(h0_n + keep_rows, s_h), pl.ds(w0_n, t_in)],
                row_buf.at[:, :t_in], sems.at[SEM_ROW]).start()

    if col_delta:
        @pl.when((~is_last) & (~nxt_turn))
        def _prefetch_col():
            delta_off_n = ov_w * moving_right(i_n, zigzag)
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(h0_n, h_k), pl.ds(w0_n + delta_off_n, nw)],
                col_buf.at[:, :nw], sems.at[SEM_COL]).start()

    _tap_dots(win_buf, w_ref, o_ref, t_run=t_run, s_w=s_w, h_k=h_k,
              w_k=w_k, rows_used=rows_used, ph=ph, g=g, c_in=c_in,
              precision=precision)


@functools.partial(jax.jit, static_argnames=(
    "t_run", "s_h", "s_w", "order", "interpret"))
def conv2d_offload_planned(x: jax.Array, w: jax.Array, *,
                           t_run: int, s_h: int = 1, s_w: int = 1,
                           order: str = "zigzag",
                           interpret: bool | None = None) -> jax.Array:
    """Plan-shaped S1 Pallas convolution: per-step DMA == plan I_slice.

    Args:
      x: input (H_in, W_in, C_in) — already padded (paper Remark 2).
      w: kernels (H_K, W_K, C_in, N).
      t_run: patches per grid step, the row-run of output columns one
        step computes; it must divide ``W_out``
        (``kernels.emit.grid_solve`` plans one that does).
      s_h, s_w: the vertical and horizontal strides.
      order: the grid sweep, "zigzag" (paper Sec 7.2: odd rows run right
        to left) or "row" (every row left to right).

    Returns the (H_out, W_out, N) output.  The traffic contract: each
    grid step fetches exactly the pixels the corresponding
    ``GroupedStrategy`` step charges to ``t_l`` (the window overlap with
    the previous step stays resident in VMEM), and the fetch is
    prefetched one step ahead.  ``kernels.emit`` maps ``LayerPlan``s
    here; ``repro.analysis.kerncheck`` proves the equivalence statically.
    f32 operands are dotted at full precision; bf16 stays bf16 with f32
    accumulation.  A step issues ``dots_per_step`` dots: taps share a dot
    where ``tap_group`` allows, with Λ as ``pack_taps`` lays it out.
    """
    if order not in ("zigzag", "row"):
        raise KernelShapeError(f"unknown grid order {order!r}")
    h_in, w_in, c_in = x.shape
    h_k, w_k, _, n = w.shape
    h_out, w_out_tiles = _conv_geometry(x.shape, w.shape, t_run, s_h, s_w)
    t_in = t_in_cols(t_run, s_w, w_k)
    nw = t_run * s_w
    dt = x.dtype
    precision = (jax.lax.Precision.HIGHEST if dt == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    pix = pixel_shape(c_in, dt)
    # an f32 pixel of more than one lane tile lies as ph rows of 128 lanes
    ph = pix[0] // 128 if len(pix) == 1 else 1
    tail = pix if ph == 1 else (128,)
    g = tap_group(c_in, dt)
    x = _to_pixels(x, 2, pix)
    w = (pack_taps(w, g) if g > 1
         else _to_pixels(w, 2, pix if ph == 1 else (ph, 128)))
    if ph > 1:
        x = x.reshape(h_in, w_in * ph, 128)
    interpret = resolve_interpret(interpret)
    if not interpret:
        # XLA would keep a small input in VMEM; the kernel DMAs from HBM.
        x = pltpu.with_memory_space_constraint(x, pltpu.HBM)
    scratch = [_dma_buffer((rows, cols * ph), tail) for rows, cols in (
        (h_k, t_in),                                         # resident window
        (h_k, nw),                                           # column delta
        (max(1, min(s_h, h_k)), t_in),                       # row delta
    )]
    vmem_bytes = (2 * _padded_vmem_bytes(w.shape, w.dtype)   # Λ (2 buffers)
                  + 2 * _padded_vmem_bytes((t_run, n), dt)   # output blocks
                  + sum(_padded_vmem_bytes(s, dt) for s in scratch))

    zig = order == "zigzag"
    kernel = functools.partial(
        _conv_planned_kernel, t_run=t_run, s_h=s_h, s_w=s_w, h_k=h_k,
        w_k=w_k, h_out=h_out, w_out_tiles=w_out_tiles, zigzag=zig,
        rows_used=-(-c_in // pix[-1]), ph=ph, g=g, c_in=c_in,
        precision=precision)
    out = pl.pallas_call(
        kernel,
        grid=(h_out, w_out_tiles),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.HBM),            # x stays in HBM
            pl.BlockSpec(w.shape, lambda i, jt: (0,) * w.ndim),  # Λ resident
        ],
        out_specs=pl.BlockSpec(
            (None, None, t_run, n),
            lambda i, jt: (i, eff_tile(i, jt, w_out_tiles, zig), 0, 0)),
        out_shape=jax.ShapeDtypeStruct((h_out, w_out_tiles, t_run, n), dt),
        scratch_shapes=[*(pltpu.VMEM(s, dt) for s in scratch),
                        pltpu.SemaphoreType.DMA((3,))],
        # The prefetch crosses grid steps: the grid must run in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes + _VMEM_HEADROOM_BYTES),
        interpret=interpret,
    )(x, w)
    return out.reshape(h_out, w_out_tiles * t_run, n)
