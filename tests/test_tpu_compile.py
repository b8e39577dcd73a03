"""The emitted conv kernels and the network executor compile for a TPU v5e.

Interpret mode cannot see what the chip's compiler refuses (unaligned
blocks and slices, unsupported relayouts, an input left in VMEM), so
these tests compile at the real ``resnet8``, ``lenet5`` and ResNet-50
widths for a described ``v5e:2x2`` topology, with ``interpret=False``.
Nothing runs.  The topology is described inside a fixture: only one
process may load the TPU library, and only the worker that runs this
file does.
"""
import functools
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.kerncheck import network_budget
from repro.configs.networks import NETWORKS
from repro.core.cost_model import HardwareModel
from repro.kernels.conv2d_offload import (
    conv2d_offload_planned, dots_per_step)
from repro.kernels.emit import (
    SHAPE_KEYS, emit_layer_kernel, execute_network, plan_emitable_network,
    plan_layers)

NETS = ("resnet8", "lenet5")
DTYPES = ("float32", "bfloat16")
RESNET50 = json.loads((Path(__file__).resolve().parents[1] / "benchmarks"
                       / "chip" / "configs" / "resnet50-f32.json").read_text())


@functools.cache
def _plan(name):
    specs = list(NETWORKS[name])
    return plan_emitable_network(specs, network_budget(specs), name=name)


def _distinct_layers():
    """(network, layer index) of each distinct emitted kernel shape."""
    seen, out = set(), []
    for name in NETS:
        for lp in _plan(name).layers:
            e = emit_layer_kernel(lp)
            key = (lp.spec, e.t_run, e.order)
            if key not in seen:
                seen.add(key)
                out.append((name, lp.index))
    return out


@functools.cache
def _resnet50_plan():
    return plan_layers(RESNET50["layers"], HardwareModel(**RESNET50["budget"]),
                       name="resnet50")


def _resnet50_distinct():
    """Index of the first layer of each distinct ResNet-50 conv shape."""
    seen, out = set(), []
    for k, layer in enumerate(RESNET50["layers"]):
        key = tuple(layer[s] for s in SHAPE_KEYS)
        if key not in seen:
            seen.add(key)
            out.append(k)
    return out


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache; keep it out of the cache."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_layer(one_chip, lp, dtype):
    e, s = emit_layer_kernel(lp), lp.spec
    x = jax.ShapeDtypeStruct((s.h_in, s.w_in, s.c_in), dtype,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((s.h_k, s.w_k, s.c_in, s.c_out), dtype,
                             sharding=one_chip)
    fn = functools.partial(conv2d_offload_planned, t_run=e.t_run,
                           s_h=s.s_h, s_w=s.s_w, order=e.order,
                           interpret=False)
    hlo = jax.jit(fn).lower(x, w).compile().as_text()
    assert "tpu_custom_call" in hlo
    # the input is pinned to HBM (memory-space color 0): the kernel DMAs
    # its windows out of HBM and would misread an input placed in VMEM
    assert '"input_memory_space_colors":[{"operand_index":"0","color":"0"' \
        in hlo
    return hlo


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name,index", _distinct_layers())
def test_planned_conv_compiles_for_v5e(one_chip, name, index, dtype):
    _compile_layer(one_chip, _plan(name).layers[index], dtype)


@pytest.mark.parametrize("index", _resnet50_distinct())
def test_resnet50_conv_compiles_for_v5e(one_chip, index):
    """The strided, 1x1, 7x7, wide and FC kernels: each distinct f32
    conv shape of ResNet-50 v1.5."""
    _compile_layer(one_chip, _resnet50_plan().layers[index], "float32")


@pytest.mark.parametrize("index,c_in,k,dots", [(0, 3, 7, 2), (3, 64, 3, 5)])
def test_narrow_resnet50_convs_compile_with_taps_sharing_dots(
        one_chip, index, c_in, k, dots):
    """The 7x7/2 stem on 3 channels and a 3x3 on 64: the compiled
    kernel takes Λ packed as (dots, 128, n), 42 and 2 taps a dot."""
    lp = _resnet50_plan().layers[index]
    s = lp.spec
    assert (s.c_in, s.h_k, s.w_k) == (c_in, k, k)
    assert dots_per_step(k, k, c_in, "float32") == dots
    call = next(line for line in _compile_layer(one_chip, lp, "float32")
                .splitlines() if "tpu_custom_call" in line)
    assert f"f32[{dots},128,{s.c_out}]{{" in call


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", NETS)
def test_execute_network_compiles_for_v5e(one_chip, name, dtype):
    plan = _plan(name)
    specs = [lp.spec for lp in plan.layers]
    s0 = specs[0]
    x = jax.ShapeDtypeStruct((s0.c_in, s0.h_in, s0.w_in), dtype,
                             sharding=one_chip)
    ws = [jax.ShapeDtypeStruct((s.c_out, s.c_in, s.h_k, s.w_k), dtype,
                               sharding=one_chip) for s in specs]
    compiled = jax.jit(lambda x, ws: execute_network(
        plan, x, ws, interpret=False)).lower(x, ws).compile()
    assert compiled.as_text().count("tpu_custom_call") >= len(specs)
    last = specs[-1]
    assert compiled.out_info.shape == (last.c_out, last.h_out, last.w_out)
    assert compiled.out_info.dtype == jnp.dtype(dtype)


def test_resnet50_network_compiles_in_layer_order_for_v5e(one_chip):
    """One program of 54 conv kernels, the joins, ReLUs and pools between
    them, and its kernels scheduled in the file's layer order (the
    projection shortcut before the first 1x1 that reads the same
    tensor)."""
    plan = _resnet50_plan()
    specs = [lp.spec for lp in plan.layers]
    s0 = specs[0]
    x = jax.ShapeDtypeStruct((s0.c_in, s0.h_in, s0.w_in), "float32",
                             sharding=one_chip)
    ws = [jax.ShapeDtypeStruct((s.c_out, s.c_in, s.h_k, s.w_k), "float32",
                               sharding=one_chip) for s in specs]
    compiled = jax.jit(lambda x, ws: execute_network(
        plan, x, ws, interpret=False)).lower(x, ws).compile()
    assert compiled.out_info.shape == (1000, 1, 1)
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    kernels = re.findall(r"= f32\[([0-9,]+)\]\{[^}]*\} custom-call\("
                         r"[^)]*\), custom_call_target=\"tpu_custom_call\"",
                         entry)
    expected = []
    for lp in plan.layers:
        e, s = emit_layer_kernel(lp), lp.spec
        expected.append(f"{s.h_out},{s.w_out // e.t_run},{e.t_run},"
                        f"{s.c_out}")
    assert kernels == expected
