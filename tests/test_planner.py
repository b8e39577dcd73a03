"""Planner property tests (hypothesis): the offloading-schedule chooser
must always respect VMEM, cover the problem, and price durations
consistently with the paper's model.  Deterministic planner tests live in
test_planner_basic.py; this module skips cleanly without hypothesis."""
import pytest

pytest.importorskip("hypothesis")

import hypothesis
from hypothesis import given, settings, strategies as st

from repro.core import planner
from repro.core.conv_spec import ConvSpec
from repro.core.cost_model import TPU_V5E, HardwareModel
from repro.core.network_planner import LayerPlan
from repro.kernels.emit import (
    emit_layer_kernel, grid_solve, kernel_vmem_elements)


@settings(max_examples=25, deadline=None)
@given(m=st.integers(128, 8192), n=st.integers(128, 8192),
       k=st.integers(128, 8192), dtype_bytes=st.sampled_from([2, 4]))
def test_property_matmul_plan_invariants(m, n, k, dtype_bytes):
    p = planner.plan_matmul(m, n, k, dtype_bytes=dtype_bytes)
    assert p.vmem_bytes <= TPU_V5E.vmem_bytes
    assert p.flops == 2 * m * n * k
    # compulsory traffic lower bound: A+B read once, C written once
    assert p.hbm_bytes >= dtype_bytes * (m * k + k * n + m * n)
    assert p.duration_overlapped <= p.duration_additive
    assert p.duration_overlapped >= p.flops / TPU_V5E.peak_flops - 1e-12
    assert p.steps >= 1


@settings(max_examples=25, deadline=None)
@given(s_log=st.integers(9, 19), d=st.sampled_from([64, 128, 256]),
       g=st.integers(1, 16))
def test_property_decode_plan_invariants(s_log, d, g):
    s = 1 << s_log
    p = planner.plan_decode_attention(s, d, g, dtype_bytes=2)
    assert s % p.tiles["bkv"] == 0
    assert p.vmem_bytes <= TPU_V5E.vmem_bytes
    # decode is memory-bound: duration == KV bytes / bw
    assert abs(p.duration_overlapped - p.hbm_bytes / TPU_V5E.hbm_bw) < 1e-12


@settings(max_examples=20, deadline=None)
@given(hw_in=st.integers(8, 40), c_in=st.integers(1, 8),
       n=st.integers(1, 16), kk=st.sampled_from([1, 3, 5]),
       p=st.integers(1, 64), room=st.one_of(st.none(), st.floats(0, 1)))
def test_property_grid_solve_invariants(hw_in, c_in, n, kk, p, room):
    """The conv planner's choice is a run the emitted kernel realises,
    fits the memory it is given, and prices no lower than the bound.
    ``room`` places ``size_mem`` between what the one-patch run and the
    whole-row run occupy (None: unbounded)."""
    hypothesis.assume(hw_in > kk)
    spec = ConvSpec(c_in, hw_in, hw_in, n, kk, kk)
    least = kernel_vmem_elements(spec, 1)
    size_mem = (None if room is None else least + int(
        room * (kernel_vmem_elements(spec, spec.w_out) - least)))
    hw = HardwareModel(nbop_pe=1 << 20, size_mem=size_mem)
    res = grid_solve(spec, p, hw)
    t_run = res.strategy.as_grid().t_run
    assert spec.w_out % t_run == 0 and t_run <= p
    if size_mem is not None:
        assert kernel_vmem_elements(spec, t_run) <= size_mem
    lp = LayerPlan(index=0, spec=spec, p=p, result=res, reuse_input=False,
                   reuse_output=False, window_rows=0,
                   gross_duration=res.objective, input_load_saved=0.0,
                   write_back_saved=0.0)
    assert emit_layer_kernel(lp).t_run == t_run
    assert res.objective >= res.lower_bound
