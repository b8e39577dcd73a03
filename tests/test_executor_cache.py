"""``execute_network`` emits a plan's kernels once per plan object, on
the CPU: later calls with the same object read the emitted layers back,
equal plans that are distinct objects emit apart, an entry goes with its
plan, and a plan that emission refuses is never kept."""
import dataclasses
import gc
import weakref

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.conv_spec import ConvSpec
from repro.core.cost_model import HardwareModel
from repro.kernels import emit
from repro.kernels.emit import (
    KernelEmitError, execute_network, plan_emitable_network)
from repro.obs.metrics import REGISTRY

# Two tiny layers of their own, so that no other test's plans or
# programs share them.
SPECS = (ConvSpec(c_in=1, h_in=7, w_in=7, n_kernels=2, h_k=3, w_k=3),
         ConvSpec(c_in=2, h_in=5, w_in=5, n_kernels=3, h_k=3, w_k=3))
COUNTERS = ("executor/emit_misses", "executor/emit_hits")


def _plan():
    return plan_emitable_network(
        list(SPECS), HardwareModel(nbop_pe=2**20, size_mem=200),
        name="cache")


def _inputs(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((1, 7, 7)), jnp.float32)
    ws = [jnp.asarray(rng.standard_normal((s.c_out, s.c_in, s.h_k, s.w_k)),
                      jnp.float32) for s in SPECS]
    return x, ws


def _call_counting(plan, x, ws):
    """Run ``plan`` once; the change of each of ``COUNTERS``."""
    before = [REGISTRY.get(k) for k in COUNTERS]
    execute_network(plan, x, ws).block_until_ready()
    return [REGISTRY.get(k) - b for k, b in zip(COUNTERS, before)]


def test_repeat_call_with_the_same_plan_emits_nothing(monkeypatch):
    emitted = []

    def counting(lp):
        emitted.append(lp.index)
        return real(lp)

    real = emit.emit_layer_kernel
    monkeypatch.setattr(emit, "emit_layer_kernel", counting)
    plan = _plan()
    x, ws = _inputs(0)
    assert _call_counting(plan, x, ws) == [1, 0]
    assert emitted == [0, 1]
    assert _call_counting(plan, x, ws) == [0, 1]
    assert emitted == [0, 1]


def test_equal_plans_that_are_distinct_objects_each_emit_once():
    first = _plan()
    second = dataclasses.replace(first)
    assert second == first and second is not first
    x, ws = _inputs(1)
    counts = [_call_counting(p, x, ws)
              for p in (first, second, first, second)]
    assert counts == [[1, 0], [1, 0], [0, 1], [0, 1]]


def test_the_entry_goes_with_its_plan():
    plan = _plan()
    x, ws = _inputs(2)
    execute_network(plan, x, ws).block_until_ready()
    key, ref = id(plan), weakref.ref(plan)
    assert key in emit._EMITTED
    del plan
    gc.collect()
    assert ref() is None, "the cache keeps the plan alive"
    assert key not in emit._EMITTED


def test_the_read_back_call_matches_the_emitting_call_bit_for_bit():
    plan = _plan()
    x, ws = _inputs(3)
    first = np.asarray(execute_network(plan, x, ws))
    again = np.asarray(execute_network(plan, x, ws))
    assert first.dtype == again.dtype and first.shape == again.shape
    assert first.tobytes() == again.tobytes()


def test_a_refused_plan_raises_on_every_call_and_is_not_kept():
    plan = _plan()
    lp = plan.layers[0]
    bad = dataclasses.replace(plan, layers=(dataclasses.replace(
        lp, result=dataclasses.replace(lp.result, mode="s2")),)
        + plan.layers[1:])
    x, ws = _inputs(4)
    for _ in range(2):
        misses = REGISTRY.get("executor/emit_misses")
        with pytest.raises(KernelEmitError, match="swapping"):
            execute_network(bad, x, ws)
        assert REGISTRY.get("executor/emit_misses") == misses + 1
        assert id(bad) not in emit._EMITTED
