"""What ``execute_network`` tells a profiler trace and the metrics
registry, on the CPU: one ``executor.emit`` span, then one
``executor.launch`` span, per call; and one ``executor/traces`` count
per trace of the jitted program, none on a jit-cache hit."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.conv_spec import ConvSpec
from repro.core.cost_model import HardwareModel
from repro.kernels.emit import (
    SPANS, emit_layer_kernel, execute_network, plan_emitable_network)
from repro.obs.metrics import REGISTRY

# Two tiny layers no other test plans, so the jit cache holds none of
# their programs when this file starts.  The budgets give the layers
# t_run (3, 1), (6, 2) and (6, 4).
SPECS = (ConvSpec(c_in=1, h_in=8, w_in=8, n_kernels=2, h_k=3, w_k=3),
         ConvSpec(c_in=2, h_in=6, w_in=6, n_kernels=2, h_k=3, w_k=3))
NBOP = 2**20


def _plan(size_mem):
    return plan_emitable_network(
        list(SPECS), HardwareModel(nbop_pe=NBOP, size_mem=size_mem),
        name="spans")


def _inputs(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((1, 8, 8)), jnp.float32)
    ws = [jnp.asarray(rng.standard_normal((s.c_out, s.c_in, s.h_k, s.w_k)),
                      jnp.float32) for s in SPECS]
    return x, ws


def test_each_call_emits_then_launches_in_the_trace(tmp_path):
    from jax.profiler import ProfileData
    plan = _plan(72)
    x, ws = _inputs(0)
    calls = 2
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(calls):
            execute_network(plan, x, ws).block_until_ready()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    spans = {name: [] for name in SPANS}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in spans:
                    spans[e.name].append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    emits, launches = (sorted(spans[name]) for name in SPANS)
    assert len(emits) == len(launches) == calls
    for k, ((_, emit_end), (launch_start, _)) in enumerate(
            zip(emits, launches)):
        assert emit_end <= launch_start
        if k + 1 < calls:   # this call's launch ends before the next emits
            assert launches[k][1] <= emits[k + 1][0]


def test_traces_count_jit_cache_misses_only():
    x, ws = _inputs(1)
    first, other = _plan(150), _plan(100)
    # unequal emitted layers make a new program; equal ones reuse it
    assert [emit_layer_kernel(lp).t_run for lp in first.layers] == [6, 4]
    assert [emit_layer_kernel(lp).t_run for lp in other.layers] == [6, 2]
    counts = []
    for plan in (first, first, other):
        before = REGISTRY.get("executor/traces")
        execute_network(plan, x, ws).block_until_ready()
        counts.append(REGISTRY.get("executor/traces") - before)
    assert counts == [1, 0, 1]

