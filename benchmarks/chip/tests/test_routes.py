"""The harness hands a configuration's layers to the program: shape keys
alone take the ``ConvSpec`` route as before; any other key sends the
layer list, as written, to ``emit.plan_layers``."""
import hashlib
import json

import numpy as np
import pytest

import counts
import run

FIXTURE = run.HERE / "tests" / "data" / "keyed-f32.json"
REAL = ["lenet5-f32", "resnet8-f32"]


def _config(name):
    return json.loads((run.HERE / "configs" / f"{name}.json").read_text())


def _fixture():
    return json.loads(FIXTURE.read_text())


def _stripped(cfg):
    return dict(cfg, layers=[{k: layer[k] for k in run.SHAPE_KEYS}
                             for layer in cfg["layers"]])


@pytest.fixture
def emit():
    from repro.kernels import emit
    return emit


def test_plan_layers_gets_the_layers_as_written(monkeypatch, emit):
    cfg = _fixture()
    calls = []

    def plan_layers(layers, hw, **kwargs):
        calls.append((layers, hw, kwargs))
        return "plan"
    monkeypatch.setattr(emit, "plan_layers", plan_layers, raising=False)
    assert run.plan_network(cfg, emit) == "plan"
    (layers, hw, kwargs), = calls
    assert layers == _fixture()["layers"]
    assert [list(layer) for layer in layers] == \
        [list(layer) for layer in _fixture()["layers"]]
    assert (hw.nbop_pe, hw.size_mem) == (cfg["budget"]["nbop_pe"],
                                         cfg["budget"]["size_mem"])
    assert kwargs == {"name": "keyed-f32", "verify": True}


@pytest.mark.parametrize("name", REAL)
def test_shape_keys_alone_take_the_convspec_route(monkeypatch, emit, name):
    from repro.core.conv_spec import ConvSpec
    cfg = _config(name)
    calls = []
    real = emit.plan_emitable_network

    def plan_emitable_network(specs, hw, **kwargs):
        calls.append((specs, hw, kwargs))
        return real(specs, hw, **kwargs)

    def plan_layers(*args, **kwargs):
        raise AssertionError("plan_layers called for shape keys alone")
    monkeypatch.setattr(emit, "plan_emitable_network", plan_emitable_network)
    monkeypatch.setattr(emit, "plan_layers", plan_layers, raising=False)
    plan = run.plan_network(cfg, emit)
    (specs, hw, kwargs), = calls
    assert specs == [ConvSpec(**layer) for layer in cfg["layers"]]
    assert (hw.nbop_pe, hw.size_mem) == (cfg["budget"]["nbop_pe"],
                                         cfg["budget"]["size_mem"])
    assert kwargs == {"name": name, "verify": True}
    assert len(plan.layers) == len(cfg["layers"])


def _digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name, digest", [
    ("lenet5-f32",
     "aa260956bbf07c69377183a934aa717ca82da71466f0eaa09a14bac7f7e3fbcf"),
    ("resnet8-f32",
     "b6f204d9e6732242e28c6ca59edb4b467bbaf0ac9ecb0065df341f4898f93d8e"),
])
def test_inputs_of_the_real_configs_are_unchanged(name, digest):
    """The weights and images, bit for bit, as the harness made them
    before configurations could carry program keys (CPU)."""
    weights, images = run.make_inputs(_config(name), 256, 2**33 + 7)
    assert _digest([*weights, *images]) == digest


def test_program_keys_change_no_input_and_no_count():
    cfg = _fixture()
    bare = _stripped(cfg)
    assert any(set(layer) != set(run.SHAPE_KEYS) for layer in cfg["layers"])
    seed = 2**31 + 99
    weights, images = run.make_inputs(cfg, 256, seed)
    bare_weights, bare_images = run.make_inputs(bare, 256, seed)
    assert images[0].shape == (1, 32, 32)
    assert _digest([*weights, *images]) == \
        _digest([*bare_weights, *bare_images])
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for count in (counts.network_macs, counts.network_flops):
        assert count(cfg) == count(bare)
    assert counts.network_bytes(cfg) == counts.network_bytes(bare)
    assert counts.least_seconds(cfg, peak) == counts.least_seconds(bare, peak)


def test_conv_chain_refuses_program_keys():
    conv_chain = run.load_module("references", "conv_chain")
    with pytest.raises(ValueError, match="relu"):
        conv_chain.make_forward(_fixture())
    with pytest.raises(run.BenchError, match="relu"):
        run.reference_forward(_fixture())
    conv_chain.make_forward(_stripped(_fixture()))


@pytest.fixture
def keyed_cell(tmp_path, monkeypatch):
    """A benchmark of one cell, of the fixture under the sync mix."""
    bench = {"configs": [{"name": "keyed-f32",
                          "file": str(FIXTURE.relative_to(run.ROOT))}],
             "workloads": [{"name": "keyed-f32.sync", "config": "keyed-f32",
                            "traffic": "sync", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    monkeypatch.setattr(run, "BENCH_FILE", path)
    return ("--workload", "keyed-f32.sync", "--seed", str(2**32 + 5),
            "--seconds", "0.5")


def _refused(capsys, cell):
    """``run.main`` on ``cell`` (with the device check faked by
    ``bench_run``) refuses it: no result, the reason on standard
    error."""
    code = run.main(list(cell))
    out, err = capsys.readouterr()
    assert code == run.EXIT_REFUSED and out == ""
    return err


def test_a_program_without_plan_layers_refuses_the_cell(
        bench_run, capsys, monkeypatch, emit, keyed_cell):
    monkeypatch.delattr(emit, "plan_layers", raising=False)
    err = _refused(capsys, keyed_cell)
    assert "plan_layers" in err and "keyed-f32" in err


def test_a_plan_layers_that_raises_refuses_the_cell(
        bench_run, capsys, monkeypatch, emit, keyed_cell):
    def plan_layers(layers, hw, **kwargs):
        raise NotImplementedError("no kernel applies a ReLU yet")
    monkeypatch.setattr(emit, "plan_layers", plan_layers, raising=False)
    err = _refused(capsys, keyed_cell)
    assert "no kernel applies a ReLU yet" in err


def test_a_fault_in_plan_layers_is_not_a_refusal(monkeypatch, emit):
    def plan_layers(layers, hw, **kwargs):
        raise KeyError("kind")
    monkeypatch.setattr(emit, "plan_layers", plan_layers, raising=False)
    with pytest.raises(KeyError, match="kind"):
        run.plan_network(_fixture(), emit)


def test_a_layer_without_a_shape_key_is_refused(tmp_path, monkeypatch):
    cfg = _fixture()
    del cfg["layers"][1]["s_w"]
    config = tmp_path / "keyed-f32.json"
    config.write_text(json.dumps(cfg))
    bench = {"configs": [{"name": "keyed-f32", "file": str(config)}],
             "workloads": [{"name": "keyed-f32.sync", "config": "keyed-f32",
                            "traffic": "sync", "chips": 1}]}
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    monkeypatch.setattr(run, "BENCH_FILE", path)
    with pytest.raises(run.BenchError, match=r"layer 1 .* \['s_w'\]"):
        run.load_cell("keyed-f32.sync")
