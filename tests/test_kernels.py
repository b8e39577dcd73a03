"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import planner
from repro.kernels import KernelShapeError, ops, ref
from repro.kernels import block_matmul as _bm
from repro.kernels import conv2d_offload as _conv
from repro.kernels import flash_decode as _fd

RNG = np.random.default_rng(42)


# ----------------------------- block_matmul --------------------------- #

@pytest.mark.parametrize("m,n,k,bm,bn,bk", [
    (64, 64, 64, 32, 32, 32),
    (200, 150, 300, 64, 64, 64),
    (128, 128, 128, 128, 128, 128),
    (96, 257, 130, 32, 64, 64),
])
@pytest.mark.parametrize("order", ["mnk", "nmk", "mkn", "knm"])
def test_matmul_shapes_orders(m, n, k, bm, bn, bk, order):
    a = RNG.standard_normal((m, k)).astype(np.float32)
    b = RNG.standard_normal((k, n)).astype(np.float32)
    out = ops.matmul(a, b, bm=bm, bn=bn, bk=bk, order=order)
    np.testing.assert_allclose(out, a @ b, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_matmul_dtypes(dtype):
    a = RNG.standard_normal((64, 96)).astype(dtype)
    b = RNG.standard_normal((96, 64)).astype(dtype)
    out = ops.matmul(a, b, bm=32, bn=32, bk=32, order="mnk")
    exp = np.asarray(a, np.float32) @ np.asarray(b, np.float32)
    tol = 1e-3 if dtype == np.float32 else 2.0
    np.testing.assert_allclose(np.asarray(out, np.float32), exp,
                               rtol=tol, atol=tol)


# ----------------------------- flash_decode --------------------------- #

@pytest.mark.parametrize("b,hq,hkv,d,s,bkv", [
    (1, 4, 4, 32, 128, 64),       # MHA
    (2, 8, 2, 64, 256, 64),       # GQA 4:1
    (2, 8, 1, 64, 256, 128),      # MQA
    (1, 16, 4, 128, 512, 256),
])
def test_decode_attention(b, hq, hkv, d, s, bkv):
    q = RNG.standard_normal((b, hq, d)).astype(np.float32)
    k = RNG.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = RNG.standard_normal((b, s, hkv, d)).astype(np.float32)
    lengths = RNG.integers(1, s + 1, size=(b,)).astype(np.int32)
    out = ops.decode_attention(q, k, v, jnp.asarray(lengths), bkv=bkv)
    g = hq // hkv
    for bi in range(b):
        for h in range(hq):
            exp = ref.decode_attention(
                jnp.asarray(q[bi, h:h + 1]), jnp.asarray(k[bi, :, h // g]),
                jnp.asarray(v[bi, :, h // g]), int(lengths[bi]))[0]
            np.testing.assert_allclose(out[bi, h], exp, rtol=2e-3, atol=2e-3)


def test_decode_attention_full_length_default():
    q = RNG.standard_normal((1, 4, 32)).astype(np.float32)
    k = RNG.standard_normal((1, 128, 4, 32)).astype(np.float32)
    v = RNG.standard_normal((1, 128, 4, 32)).astype(np.float32)
    out = ops.decode_attention(q, k, v, bkv=32)
    exp = ops.decode_attention(q, k, v, jnp.asarray([128], jnp.int32),
                               bkv=32)
    np.testing.assert_allclose(out, exp, rtol=1e-5, atol=1e-5)


# ------------------------------- planner ------------------------------ #

def test_planner_matmul_fits_vmem_and_prefers_reuse():
    p = planner.plan_matmul(8192, 8192, 8192, dtype_bytes=2)
    assert p.vmem_bytes <= planner.TPU_V5E.vmem_bytes
    # compute-bound at this size: overlapped duration == flops/peak
    assert abs(p.duration_overlapped - p.flops / planner.TPU_V5E.peak_flops) \
        / p.duration_overlapped < 1e-6
    # bytes moved must be >= the compulsory traffic (A+B+C once)
    compulsory = 2 * (8192 * 8192 * 3)
    assert p.hbm_bytes >= compulsory


def test_planner_decode_attention_is_memory_bound():
    p = planner.plan_decode_attention(32768, 128, 8, dtype_bytes=2)
    t_mem = p.hbm_bytes / planner.TPU_V5E.hbm_bw
    assert p.duration_overlapped == t_mem      # decode: always memory-bound
    assert 32768 % p.tiles["bkv"] == 0


def test_planner_duration_models_ordering():
    p = planner.plan_matmul(1024, 1024, 1024, dtype_bytes=2)
    assert p.duration_overlapped <= p.duration_additive


# ----------------------- conv2d_offload_planned ----------------------- #

@pytest.mark.parametrize("order", ["zigzag", "row"])
@pytest.mark.parametrize("c_in,h,w,n,kh,kw,sh,sw,t_run", [
    (2, 10, 12, 3, 3, 3, 1, 1, 5),     # col-delta within rows + row turns
    (1, 9, 9, 2, 3, 3, 1, 1, 7),       # one tile per row: row-delta only
    (2, 11, 13, 3, 3, 3, 2, 2, 3),     # strides 2: every window disjoint rows
    (3, 12, 14, 4, 5, 3, 1, 2, 2),     # tall kernel, stride-2 columns
    (1, 8, 8, 2, 1, 1, 1, 1, 4),       # 1x1 kernel: full fetch per tile
    (2, 13, 11, 3, 3, 3, 3, 1, 9),     # s_h >= h_k: no row-to-row reuse
    (1, 6, 6, 1, 3, 3, 1, 1, 2),       # one channel, one kernel
    (3, 12, 14, 5, 3, 3, 1, 1, 4),
    (2, 9, 11, 4, 2, 2, 1, 1, 5),      # even kernel
    (4, 16, 16, 8, 5, 5, 1, 1, 4),     # 5x5: 25 taps in one tap group
    (1, 8, 8, 2, 1, 1, 1, 1, 8),       # 1x1 kernel, one tile per row
])
def test_conv_planned_delta_fetch_matches_ref(order, c_in, h, w, n, kh, kw,
                                              sh, sw, t_run):
    """The double-buffered delta-fetch kernel (the one kerncheck proves)
    must equal the reference conv across stride/order/tile crossings —
    the same geometry cases the static trace enumerates."""
    x = RNG.standard_normal((c_in, h, w)).astype(np.float32)
    k = RNG.standard_normal((n, c_in, kh, kw)).astype(np.float32)
    out = _conv.conv2d_offload_planned(
        jnp.asarray(x.transpose(1, 2, 0)), jnp.asarray(k.transpose(2, 3, 1, 0)),
        t_run=t_run, s_h=sh, s_w=sw, order=order, interpret=True)
    exp = ref.conv2d(jnp.asarray(x), jnp.asarray(k), sh, sw)
    np.testing.assert_allclose(out, np.transpose(exp, (1, 2, 0)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("order", ["zigzag", "row"])
def test_conv_planned_bf16_matches_ref(order):
    """bf16 operands stay bf16 on the MXU with f32 accumulation."""
    x = RNG.standard_normal((2, 10, 12)).astype(jnp.bfloat16)
    k = RNG.standard_normal((3, 2, 3, 3)).astype(jnp.bfloat16)
    out = _conv.conv2d_offload_planned(
        jnp.asarray(x.transpose(1, 2, 0)), jnp.asarray(k.transpose(2, 3, 1, 0)),
        t_run=5, order=order, interpret=True)
    exp = ref.conv2d(jnp.asarray(x), jnp.asarray(k))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.transpose(np.asarray(exp, np.float32),
                                            (1, 2, 0)),
                               rtol=5e-2, atol=5e-2)


def test_kernel_geometry_errors_are_typed():
    """Bare asserts were replaced by KernelShapeError raises (lint L006
    now covers kernels/): bad geometry must raise the typed error, not
    AssertionError, and survive python -O."""
    x_hwc = jnp.zeros((8, 8, 2), jnp.float32)
    k_hwc = jnp.zeros((3, 3, 2, 3), jnp.float32)
    with pytest.raises(KernelShapeError):
        _conv.conv2d_offload_planned(x_hwc, k_hwc, t_run=4, order="spiral",
                                     interpret=True)
    with pytest.raises(KernelShapeError):      # t_run does not divide w_out
        _conv.conv2d_offload_planned(x_hwc, k_hwc, t_run=4, s_w=1, s_h=1,
                                     order="zigzag", interpret=True)
    with pytest.raises(KernelShapeError):      # channel mismatch
        _conv.conv2d_offload_planned(
            x_hwc, jnp.zeros((3, 3, 1, 3), jnp.float32), t_run=3,
            interpret=True)
    a = jnp.zeros((64, 64), jnp.float32)
    with pytest.raises(KernelShapeError):      # tiles must divide dims
        _bm.block_matmul(a, a, bm=48, bn=32, bk=32, order="mnk",
                         interpret=True)
    with pytest.raises(KernelShapeError):      # bad order permutation
        _bm.block_matmul(a, a, bm=32, bn=32, bk=32, order="mmk",
                         interpret=True)
    q = jnp.zeros((4, 32), jnp.float32)
    kv = jnp.zeros((128, 16), jnp.float32)
    with pytest.raises(KernelShapeError):      # head-dim mismatch
        _fd.decode_attention(q, kv, kv, jnp.int32(128), bkv=64,
                             interpret=True)
