"""The harness refuses what it cannot measure, and finds every cell's
pieces by name."""
import json
import re

import pytest

import run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
PEAKS = json.loads((run.HERE / "peaks.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_refuses_the_cpu():
    with pytest.raises(run.BenchError, match="needs a TPU"):
        run.device_info(1, PEAKS)


@pytest.mark.parametrize("devices, chips, match", [
    ([Device("tpu", "TPU v9 imaginary")], 1, "no row in peaks.json"),
    ([Device("tpu", "TPU v5 lite")], 4, "needs 4 chips"),
    ([Device("gpu", "TPU v5 lite")], 1, "needs a TPU"),
])
def test_refuses_devices_it_cannot_measure(monkeypatch, devices, chips,
                                           match):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: devices)
    with pytest.raises(run.BenchError, match=match):
        run.device_info(chips, PEAKS)


def test_a_cpu_run_exits_without_a_result(monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(run.ROOT / ".jax_compile_cache"))
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    code = run.main(["--workload", "lenet5-f32.sync", "--seed", "1",
                     "--seconds", "1"])
    out, err = capsys.readouterr()
    assert code == run.EXIT_REFUSED and out == ""
    assert "needs a TPU" in err


def test_unknown_workload_is_refused():
    with pytest.raises(run.BenchError, match="no workload"):
        run.load_cell("resnet8-f32.nonesuch")


@pytest.mark.parametrize("cell", [c["name"] for c in BENCH["workloads"]])
def test_every_cell_finds_its_pieces(cell):
    _, entry, cfg, traffic = run.load_cell(cell)
    assert cfg["name"] == entry["config"]
    run.load_module("runners", traffic["runner"])
    run.load_module("references", cfg["reference"])
    ends = run.select_metrics(BENCH, cell, per_layer=False)
    layers = run.select_metrics(BENCH, cell, per_layer=True)
    names = {m["name"] for m in ends}
    assert "setup_s" in names and len(names) >= 2
    assert layers and all(m["moves"] in names for m in layers)
    for m in ends + layers:
        assert hasattr(run.load_module("metrics", m["name"].split(".")[0]),
                       "reduce")


def test_benchmark_names_and_files():
    entries = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] \
        + BENCH["per_layer"]
    assert all(NAME.match(e["name"]) for e in entries)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
    for c in BENCH["configs"]:
        assert (run.ROOT / c["file"]).is_file()
        cfg = json.loads((run.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert layers == {"planner", "executor", "kernel", "device"}
