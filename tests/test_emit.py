"""Plan -> kernel emission (ISSUE 7): ``as_grid`` recognises exactly the
uniform sweep strategies, ``grid_solve`` only returns kernel-feasible
plans, ``emit_layer_kernel`` refuses what no kernel realises, and every
emitted layer of the registered networks executes (interpret mode) to
the reference convolution."""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs.networks import NETWORKS
from repro.core.conv_spec import ConvSpec
from repro.core.strategies import row_by_row, tiled, zigzag
from repro.kernels import ref
from repro.kernels.emit import (
    KernelEmitError, emit_layer_kernel, grid_solve, kernel_vmem_elements,
    plan_emitable_network)

RNG = np.random.default_rng(7)
SPEC = ConvSpec(2, 10, 12, 3, 3, 3)


# --------------------------------------------------------------------- #
# Strategy -> grid recognition
# --------------------------------------------------------------------- #

def test_as_grid_recognises_zigzag_and_row_sweeps():
    for t in (2, 5, SPEC.w_out):
        meta = zigzag(SPEC, t).as_grid()
        assert meta is not None
        assert (meta.t_run, meta.h_out, meta.w_out_tiles) == \
            (t, SPEC.h_out, SPEC.w_out // t)
        assert meta.order == "zigzag"
        assert meta.grid == (SPEC.h_out, SPEC.w_out // t)
    meta = row_by_row(SPEC, 5).as_grid()
    assert meta is not None and meta.order == "row"


def test_as_grid_rejects_non_grid_strategies():
    assert tiled(SPEC, 6).as_grid() is None            # 2-D tiles
    assert zigzag(SPEC, 7).as_grid() is None           # 7 does not divide 12
    zz = zigzag(SPEC, 4)
    shuffled = dataclasses.replace(
        zz, groups=list(reversed(zz.groups)))
    assert shuffled.as_grid() is None                  # right runs, bad order


# --------------------------------------------------------------------- #
# Emitable solving
# --------------------------------------------------------------------- #

def test_grid_solve_respects_kernel_vmem_budget():
    from repro.core.cost_model import HardwareModel
    tight = HardwareModel(nbop_pe=1 << 20,
                          size_mem=kernel_vmem_elements(SPEC, 2))
    res = grid_solve(SPEC, 10, tight)
    meta = res.strategy.as_grid()
    assert meta is not None
    assert kernel_vmem_elements(SPEC, meta.t_run) <= tight.size_mem
    roomy = HardwareModel(nbop_pe=1 << 20, size_mem=10 ** 9)
    wide = grid_solve(SPEC, SPEC.w_out, roomy)
    assert wide.objective <= res.objective


def test_grid_solve_prefers_runs_longer_than_one_patch():
    """Grouping patches into runs beats the one-patch S1 baseline: with
    room for every run up to p, the solver takes the longest run that
    divides w_out (62 = 2 x 31: of the runs up to 16, 1 and 2)."""
    from repro.core.cost_model import HardwareModel
    spec = ConvSpec(3, 64, 64, 8, 3, 3)
    hw = HardwareModel(nbop_pe=1 << 20)
    res = grid_solve(spec, 16, hw)
    meta = res.strategy.as_grid()
    assert meta is not None and meta.t_run == 2
    assert res.objective < zigzag(spec, 1).objective(hw)


def test_grid_solve_raises_when_nothing_fits():
    from repro.core.cost_model import HardwareModel
    hw = HardwareModel(nbop_pe=1 << 20,
                       size_mem=kernel_vmem_elements(SPEC, 1) - 1)
    with pytest.raises(ValueError, match="no emitable"):
        grid_solve(SPEC, 4, hw)


# --------------------------------------------------------------------- #
# Emission refusals
# --------------------------------------------------------------------- #

def _planned_layer(spec=SPEC):
    from repro.core.cost_model import HardwareModel
    hw = HardwareModel(nbop_pe=1 << 20,
                       size_mem=kernel_vmem_elements(spec, spec.w_out))
    plan = plan_emitable_network([spec], hw, name="one")
    return plan.layers[0]


def test_emit_refuses_s2_plans():
    lp = _planned_layer()
    bad = dataclasses.replace(
        lp, result=dataclasses.replace(lp.result, mode="s2"))
    with pytest.raises(KernelEmitError, match="swapping"):
        emit_layer_kernel(bad)


def test_emit_refuses_non_grid_strategies():
    lp = _planned_layer()
    bad = dataclasses.replace(
        lp, result=dataclasses.replace(lp.result, strategy=tiled(SPEC, 6)))
    with pytest.raises(KernelEmitError, match="not a uniform grid"):
        emit_layer_kernel(bad)


def test_emit_refuses_row_order_with_overlapping_rows():
    lp = _planned_layer()
    bad = dataclasses.replace(
        lp, result=dataclasses.replace(lp.result,
                                       strategy=row_by_row(SPEC, 5)))
    with pytest.raises(KernelEmitError, match="row-order"):
        emit_layer_kernel(bad)


def test_emit_allows_row_order_single_tile():
    spec = ConvSpec(1, 8, 6, 2, 3, 3)        # w_out == 4, one tile of 4
    lp = _planned_layer(spec)
    row = dataclasses.replace(
        lp, result=dataclasses.replace(lp.result,
                                       strategy=row_by_row(spec, 4)))
    emitted = emit_layer_kernel(row)
    assert emitted.order in ("zigzag", "row")
    assert emitted.t_run == 4


# --------------------------------------------------------------------- #
# End to end: emitted kernels reproduce the reference convolution
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("name", ["lenet5", "tight2", "tight4"])
def test_emitted_network_layers_match_reference(name):
    from repro.analysis.kerncheck import network_budget
    specs = list(NETWORKS[name])
    plan = plan_emitable_network(specs, network_budget(specs), name=name)
    for lp in plan.layers:
        emitted = emit_layer_kernel(lp)
        spec = lp.spec
        x = RNG.standard_normal(
            (spec.c_in, spec.h_in, spec.w_in)).astype(np.float32)
        w = RNG.standard_normal(
            (spec.c_out, spec.c_in, spec.h_k, spec.w_k)).astype(np.float32)
        out = emitted.run(jnp.asarray(x), jnp.asarray(w))
        exp = ref.conv2d(jnp.asarray(x), jnp.asarray(w), spec.s_h,
                         spec.s_w)
        np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)
