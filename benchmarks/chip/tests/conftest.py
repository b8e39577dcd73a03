"""The benchmark's own tests, run by path: ``pytest benchmarks/chip/tests``.

They run on the CPU, where the Pallas kernels run in interpret mode.
The harness's modules sit beside ``run.py`` and are imported flat, as
``run.py`` imports them when it runs as a script.
"""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for path in (HERE, ROOT / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))


class FakeDevice:
    """Stands in for a TPU where a test skips the harness's look for one
    and drives the rest of a run on the CPU."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 0}


@pytest.fixture
def bench_run(monkeypatch, capsys):
    """Call ``run.main`` on the CPU with the device check faked; return
    (exit code, parsed last stdout line or None, stderr)."""
    import json

    import run

    def fake_device_info(chips, peaks):
        return FakeDevice(), peaks["devices"][FakeDevice.device_kind]
    monkeypatch.setattr(run, "device_info", fake_device_info)
    # run.main points these into the checkout; restore them afterwards
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(ROOT / ".jax_compile_cache"))
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    monkeypatch.setattr(run, "WARMUP_REQUESTS", 4)

    def go(*argv):
        code = run.main(list(argv))
        out, err = capsys.readouterr()
        lines = out.strip().splitlines()
        return code, (json.loads(lines[-1]) if lines else None), err
    return go
