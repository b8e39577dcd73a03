"""The benchmark's own reference, against XLA's convolution and the
program's reference chain, and its control."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import run

conv_chain = run.load_module("references", "conv_chain")


def _config(name):
    return json.loads((run.HERE / "configs" / f"{name}.json").read_text())


def _draw(seed, *shapes):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]


@pytest.mark.parametrize("c_in, hw, n, k, stride", [
    (16, 18, 32, 3, 1),       # resnet8's fourth layer
    (1, 32, 6, 5, 1),         # lenet5 C1
    (3, 11, 4, 3, 2),
])
def test_conv_matches_xla(c_in, hw, n, k, stride):
    x, w = _draw(0, (2, c_in, hw, hw), (n, c_in, k, k))
    want = lax.conv_general_dilated(
        x, w, (stride, stride), "VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)
    got = conv_chain.conv(x, w, stride, stride)
    assert got.shape == want.shape
    # f32 sums in another order: apart by rounding, relative to max|ref|
    assert run.max_rel_err(np.asarray(got), np.asarray(want)) < 1e-6


def test_adapt_pools_then_pads_centred():
    y = jnp.arange(16, dtype=jnp.float32).reshape(1, 1, 4, 4)
    out = conv_chain.adapt(y, {"h_in": 3, "w_in": 4})
    assert out.shape == (1, 1, 3, 4)
    np.testing.assert_array_equal(out[0, 0], [[0, 5, 7, 0],
                                              [0, 13, 15, 0],
                                              [0, 0, 0, 0]])


@pytest.mark.parametrize("name", ["lenet5-f32", "resnet8-f32"])
def test_forward_agrees_with_the_programs_chain(name):
    from repro.core.conv_spec import ConvSpec
    from repro.kernels.emit import reference_network
    cfg = _config(name)
    layers = cfg["layers"]
    first = layers[0]
    x, *ws = _draw(1, (first["c_in"], first["h_in"], first["w_in"]),
                   *[(la["n_kernels"], la["c_in"], la["h_k"], la["w_k"])
                     for la in layers])
    ws = [w / np.sqrt(w[0].size) for w in ws]
    ours = conv_chain.forward(cfg, x[None], ws)[0]
    theirs = reference_network([ConvSpec(**la) for la in layers], x, ws)
    assert run.max_rel_err(np.asarray(ours)[None],
                           np.asarray(theirs)[None]) < 1e-6


@pytest.mark.parametrize("name", ["lenet5-f32", "resnet8-f32"])
def test_control_fails_the_limit(name):
    """Three bf16 passes, the precision below float32 at highest, read
    above the configuration's limit; full precision reads far below."""
    cfg = _config(name)
    layers = cfg["layers"]
    first = layers[0]
    x, *ws = _draw(2, (4, first["c_in"], first["h_in"], first["w_in"]),
                   *[(la["n_kernels"], la["c_in"], la["h_k"], la["w_k"])
                     for la in layers])
    ws = [w / np.sqrt(w[0].size) for w in ws]
    exact = np.asarray(conv_chain.make_forward(cfg)(x, ws))
    with jax.default_matmul_precision("float32"):
        xla = np.asarray(conv_chain.forward(cfg, x, ws))
    control = np.asarray(conv_chain.make_forward(cfg, passes=3)(x, ws))
    limit = cfg["limits"]["max_rel_err"]
    assert run.max_rel_err(xla, exact) < limit / 3
    assert run.max_rel_err(control, exact) > limit
