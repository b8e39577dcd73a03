"""A whole run on the CPU with the harness's look for a chip skipped:
sound, it is correct; with the timed path broken underneath, or the
control in the program's place, ``correct`` comes out false."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import run

CELL = ("--workload", "lenet5-f32.sync", "--seed", str(2**31 + 12345),
        "--seconds", "0.5")


def _assert_result(code, result, err, correct):
    assert code == 0
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    assert result["correct"] is correct
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"latency_mean_ms", "setup_s"}
    (name, check), = result["checks"].items()
    # the numbers compared come last on standard error too
    assert err.strip().splitlines()[-1] == \
        f"check {name}={check['value']!r} limit={check['limit']!r}"


def test_a_sound_run_is_correct(bench_run):
    code, result, err = bench_run(*CELL)
    _assert_result(code, result, err, correct=True)
    # the plan was emitted and compiled in set-up: the window only reads
    # it back
    counters, = [line for line in err.splitlines()
                 if line.startswith("window counters: ")]
    assert counters.startswith("window counters: executor/traces=0 "
                               "executor/emit_misses=0 executor/emit_hits=")
    assert int(counters.rsplit("=", 1)[1]) == result["attempted"]


def test_a_traced_run_reports_per_layer_metrics(bench_run):
    code, result, _ = bench_run(*CELL, "--trace", "1")
    assert code == 0 and result["correct"] is True
    # the CPU has no device plane: what reads the device's events finds
    # nothing, while the program's host spans are there
    assert set(result["metrics"]) == {"plan_s", "compile_s",
                                      "dispatch_us.sync", "emit_us.sync",
                                      "launch_us.sync"}
    assert 0 < result["metrics"]["emit_us.sync"]["value"]
    assert 0 < result["metrics"]["launch_us.sync"]["value"]
    assert result["device"]["busy_s"] == 0
    assert list(result)[-1] == "checks" and "breakdown" in result


def _altered(execute):
    """Every answer gets one element changed where it is produced."""
    def broken(plan, x, weights, **kw):
        out = execute(plan, x, weights, **kw)
        return out.at[0, 0, 0].add(1e-3 * jnp.max(jnp.abs(out)))
    return broken


def _stale(execute):
    """Each request is answered with the previous request's output."""
    last = []

    def broken(plan, x, weights, **kw):
        out = execute(plan, x, weights, **kw)
        last.append(out)
        return last[-2] if len(last) > 1 else out
    return broken


def _control(execute):
    """The reference in three bf16 passes in the program's place."""
    cfg = json.loads((run.HERE / "configs" / "lenet5-f32.json").read_text())
    forward = run.load_module("references", "conv_chain").make_forward(
        cfg, passes=3)

    def broken(plan, x, weights, **kw):
        return forward(x[None], weights)[0]
    return broken


@pytest.mark.parametrize("fault", [_altered, _stale, _control])
def test_a_broken_timed_path_is_not_correct(bench_run, monkeypatch, fault):
    from repro.kernels import emit
    monkeypatch.setattr(emit, "execute_network",
                        fault(emit.execute_network))
    code, result, err = bench_run(*CELL)
    _assert_result(code, result, err, correct=False)
    check = result["checks"]["max_rel_err"]
    assert check["value"] > check["limit"]


def test_max_rel_err_is_per_image():
    refs = np.ones((2, 3, 4, 4), np.float32)
    refs[1] *= 100
    outs = refs.copy()
    outs[0, 0, 0, 0] += 0.5          # half of image 0's largest value
    outs[1, 0, 0, 0] += 1.0          # a hundredth of image 1's
    assert run.max_rel_err(outs, refs) == pytest.approx(0.5)
    assert run.max_rel_err(outs[:, :2], refs) is None
    outs[1, 0, 0, 1] = np.nan
    assert run.max_rel_err(outs, refs) is None
