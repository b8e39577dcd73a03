"""The run step of plan -> verify -> emit -> run, on the CPU.

``execute_network`` chains the emitted kernels (interpret mode here) and
must equal the plain full-precision reference chain; the planned kernel
on its (H, W, C) layout must stay exact across channel tilings and both
dtypes; interpret mode is chosen by backend; the compile cache is placed
from outside or in the checkout; and ``chip_smoke.py`` refuses to run
anywhere but on a TPU.
"""
import functools
import itertools
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compile_cache
from repro.analysis.kerncheck import network_budget
from repro.configs.networks import NETWORKS
from repro.core.conv_spec import ConvSpec
from repro.kernels import KernelShapeError, ref, resolve_interpret
from repro.kernels.conv2d_offload import (
    CASE_COL, CASE_FULL, CASE_ROW, conv2d_offload_planned, dots_per_step,
    grid_sequence, pack_taps, pixel_shape, step_case, tap_group)
from repro.kernels.emit import (
    execute_network, glue, plan_emitable_network, reference_network)

REPO = Path(__file__).resolve().parents[1]
RNG = np.random.default_rng(11)


def _weights(specs, rng):
    return [jnp.asarray(rng.standard_normal((s.c_out, s.c_in, s.h_k, s.w_k))
                        / np.sqrt(s.c_in * s.h_k * s.w_k), jnp.float32)
            for s in specs]


# --------------------------------------------------------------------- #
# Whole-network execution
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("name", ["lenet5", "tight2"])
def test_execute_network_matches_reference_chain(name):
    specs = list(NETWORKS[name])
    plan = plan_emitable_network(specs, network_budget(specs), name=name)
    s0 = specs[0]
    x = jnp.asarray(RNG.standard_normal((s0.c_in, s0.h_in, s0.w_in)),
                    jnp.float32)
    ws = _weights(specs, RNG)
    out = execute_network(plan, x, ws)
    exp = reference_network(specs, x, ws)
    last = specs[-1]
    assert out.shape == exp.shape == (last.c_out, last.h_out, last.w_out)
    np.testing.assert_allclose(out, exp, rtol=0,
                               atol=1e-5 * float(jnp.max(jnp.abs(exp))))


def test_execute_network_checks_weight_count():
    specs = list(NETWORKS["lenet5"])
    plan = plan_emitable_network(specs, network_budget(specs), name="lenet5")
    s0 = specs[0]
    x = jnp.zeros((s0.c_in, s0.h_in, s0.w_in), jnp.float32)
    with pytest.raises(KernelShapeError, match="weight tensors"):
        execute_network(plan, x, _weights(specs, RNG)[:1])


def test_glue_pools_then_pads_to_next_spec():
    y = jnp.arange(8 * 8 * 3, dtype=jnp.float32).reshape(8, 8, 3)
    # same size: zero padding only, centred
    out = glue(y, ConvSpec(3, 10, 10, 4, 3, 3))
    assert out.shape == (10, 10, 3)
    np.testing.assert_array_equal(out[1:9, 1:9], y)
    assert float(jnp.abs(out[0]).max()) == 0.0
    # larger than the next input: 2x2 max-pool, then pad
    out = glue(y, ConvSpec(3, 6, 6, 4, 3, 3))
    assert out.shape == (6, 6, 3)
    np.testing.assert_array_equal(
        out[1:5, 1:5], y.reshape(4, 2, 4, 2, 3).max(axis=(1, 3)))


@pytest.mark.parametrize("spec,msg", [
    (ConvSpec(4, 10, 10, 4, 3, 3), "channels"),     # channel mismatch
    (ConvSpec(3, 3, 3, 4, 1, 1), "does not fit"),   # pooled 4x4 > 3x3
])
def test_glue_refuses_what_it_cannot_adapt(spec, msg):
    with pytest.raises(KernelShapeError, match=msg):
        glue(jnp.zeros((8, 8, 3), jnp.float32), spec)


def test_glue_refuses_odd_pool():
    with pytest.raises(KernelShapeError, match="pool"):
        glue(jnp.zeros((7, 7, 3), jnp.float32), ConvSpec(3, 5, 5, 4, 3, 3))


# --------------------------------------------------------------------- #
# Planned kernel on the (H, W, C) layout
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("c_in", [1, 130, 300])
def test_planned_conv_channel_tiling(c_in, dtype):
    """Channels padded to lane tiles (one row per pixel in f32, two in
    bf16, more than one tile of lanes past 128 / 256 channels) still
    give the reference convolution, through all three fetch cases."""
    h, w, n, k, t = 5, 8, 3, 3, 3
    assert {step_case(i, jt, t_run=t, s_h=1, s_w=1, h_k=k, w_k=k,
                      w_out_tiles=(w - k + 1) // t, order="zigzag")
            for i, jt in grid_sequence(h - k + 1, (w - k + 1) // t)} == \
        {CASE_FULL, CASE_ROW, CASE_COL}
    x = jnp.asarray(RNG.standard_normal((h, w, c_in)), dtype)
    kern = jnp.asarray(RNG.standard_normal((k, k, c_in, n)), dtype)
    out = conv2d_offload_planned(x, kern, t_run=t, order="zigzag")
    assert out.dtype == jnp.dtype(dtype)
    exp = ref.conv2d(jnp.transpose(x, (2, 0, 1)).astype(jnp.float32),
                     jnp.transpose(kern, (3, 2, 0, 1)).astype(jnp.float32))
    exp = jnp.transpose(exp, (1, 2, 0))
    tol = 1e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), exp, rtol=0,
                               atol=tol * float(jnp.max(jnp.abs(exp))))


@pytest.mark.parametrize("c,dtype,shape", [
    (3, "float32", (128,)), (129, "float32", (256,)),
    (3, "bfloat16", (2, 128)), (256, "bfloat16", (2, 128)),
    (257, "bfloat16", (2, 256)),
])
def test_pixel_shape(c, dtype, shape):
    assert pixel_shape(c, dtype) == shape


# Taps sharing a dot: every packed width (c_in <= 64) and two unpacked
# ones, each under the four kernel sizes, with strides and orders
# alternating so that every width meets each (stride, order) pair and
# every kernel size both orders.
TAP_CASES = [(c_in, k, 1 + (i + j) % 2, ("zigzag", "row")[(i + j // 2) % 2])
             for i, c_in in enumerate((1, 3, 6, 16, 32, 64, 65, 128))
             for j, k in enumerate((1, 3, 5, 7))]


@pytest.mark.parametrize("c_in,k,s,order", TAP_CASES)
def test_planned_conv_with_taps_sharing_dots(c_in, k, s, order):
    """f32 layers whose taps share a dot (and the widths whose taps do
    not) give the plain reference convolution to the benchmark's
    ``max_rel_err`` limit."""
    t, w_out, h_out, n = 4, 8, 3, 5
    x = jnp.asarray(RNG.standard_normal(((h_out - 1) * s + k,
                                         (w_out - 1) * s + k, c_in)),
                    jnp.float32)
    kern = jnp.asarray(RNG.standard_normal((k, k, c_in, n)), jnp.float32)
    out = conv2d_offload_planned(x, kern, t_run=t, s_h=s, s_w=s,
                                 order=order)
    exp = jnp.transpose(ref.conv2d(jnp.transpose(x, (2, 0, 1)),
                                   jnp.transpose(kern, (3, 2, 0, 1)), s, s),
                        (1, 2, 0))
    assert out.shape == exp.shape == (h_out, w_out, n)
    assert (tap_group(c_in, jnp.float32) > 1) == (c_in <= 64)
    err = float(jnp.max(jnp.abs(out - exp)) / jnp.max(jnp.abs(exp)))
    assert err <= 2.5e-06


@pytest.mark.parametrize("k,c_in", [(7, 3), (3, 64), (5, 6), (3, 16),
                                    (1, 1), (5, 1)])
def test_pack_taps_puts_each_tap_in_its_lanes(k, c_in):
    """Tap (kh, kw) = t, channel c lands in dot t // g at lane
    (t % g) * c_in + c, and every lane no tap uses is zero."""
    g = tap_group(c_in, jnp.float32)
    assert g == 128 // c_in >= 2
    w = RNG.standard_normal((k, k, c_in, 4)).astype(np.float32)
    packed = np.asarray(pack_taps(jnp.asarray(w), g))
    assert packed.shape == (-(-k * k // g), 128, 4)
    assert dots_per_step(k, k, c_in, jnp.float32) == packed.shape[0]
    used = np.zeros(packed.shape[:2], bool)
    for kh, kw, c in itertools.product(range(k), range(k), range(c_in)):
        t = kh * k + kw
        np.testing.assert_array_equal(packed[t // g, t % g * c_in + c],
                                      w[kh, kw, c])
        used[t // g, t % g * c_in + c] = True
    assert not packed[~used].any()


def _lambda_shape(c_in, dtype, k=3):
    """Shape of the Λ operand ``conv2d_offload_planned`` hands its
    kernel."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn.invars[1].aval.shape
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr", p)
                if hasattr(sub, "eqns") and (shape := find(sub)):
                    return shape
        return None

    fn = functools.partial(conv2d_offload_planned, t_run=4)
    return find(jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((k + 3, k + 3, c_in), dtype),
        jax.ShapeDtypeStruct((k, k, c_in, 2), dtype)).jaxpr)


@pytest.mark.parametrize("c_in,dtype,shape,dots", [
    (3, "float32", (1, 128, 2), 1),          # packed: 9 taps in 1 dot
    (64, "float32", (5, 128, 2), 5),         # packed: 2 taps a dot
    (65, "float32", (3, 3, 128, 2), 9),
    (128, "float32", (3, 3, 128, 2), 9),
    (300, "float32", (3, 3, 3, 128, 2), 27),
    (3, "bfloat16", (3, 3, 2, 128, 2), 9),
    (64, "bfloat16", (3, 3, 2, 128, 2), 9),
])
def test_wide_and_bf16_pixels_keep_a_dot_per_tap(c_in, dtype, shape, dots):
    """Only one-row f32 pixels of at most 64 channels pack their taps;
    wider pixels and bf16 keep Λ as (h_k, w_k, *pixel, n), a dot per
    tap and pixel row."""
    assert (tap_group(c_in, dtype) > 1) == (len(shape) == 3)
    assert _lambda_shape(c_in, dtype) == shape
    assert dots_per_step(3, 3, c_in, dtype) == dots


# --------------------------------------------------------------------- #
# Interpret mode and the compile cache
# --------------------------------------------------------------------- #

def test_resolve_interpret_by_backend():
    assert jax.default_backend() == "cpu"
    assert resolve_interpret(None) is True        # no TPU: interpret
    assert resolve_interpret(False) is False      # explicit wins
    assert resolve_interpret(True) is True


@pytest.fixture
def _restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_compile_cache_in_checkout_when_unset(monkeypatch,
                                             _restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_compile_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs \
        == 0
    assert ".jax_compile_cache/" in \
        (REPO / ".gitignore").read_text().splitlines()


def test_compile_cache_env_wins(monkeypatch, tmp_path,
                                _restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX read the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


# --------------------------------------------------------------------- #
# chip_smoke.py
# --------------------------------------------------------------------- #

def test_chip_smoke_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
