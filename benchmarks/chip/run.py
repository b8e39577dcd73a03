"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose first JAX device is
a TPU (anything else exits non-zero without a result).  The cell names a
configuration (``configs/<name>.json``: layers, dtype, planner budget,
the reference that checks it and the limit of each number compared) and
a traffic mix (``traffic/<name>.json``: the runner module under
``runners/`` and its parameters).  Each metric is read by
``metrics/<name>.py``, where a name ``base.split`` is read by
``metrics/<base>.py``.  Nothing here names a configuration, a mix or a
metric.

Of each layer the harness reads only the :data:`SHAPE_KEYS`: layer 0's
``c_in, h_in, w_in`` give the image; each layer gets one weight
``(n_kernels, c_in, h_k, w_k)`` drawn from N(0, 1/fan_in); ``counts.py``
counts each layer's work from them; and the number of layers is the
number of conv kernels in a network program.  Any other key belongs to
the program and to the configuration's reference, and is passed through
untouched.  Set-up plans the network by one of two routes, chosen by the
keys alone:

* every layer holds only shape keys: ``ConvSpec(**layer)`` for each,
  then ``repro.kernels.emit.plan_emitable_network(specs, hw, name=...,
  verify=True)``;
* some layer holds another key: the layer list as written, a list of
  dicts in file order, goes to ``repro.kernels.emit.plan_layers(layers,
  hw, *, name, verify=True)``.  A program without ``plan_layers``, or a
  ``plan_layers`` that refuses the layers by raising one of
  :data:`REFUSALS` (``ValueError``, which the program's planning and
  emission errors subclass, or ``NotImplementedError``), refuses the
  run before any timing: exit ``EXIT_REFUSED``, the program's words on
  standard error, no result.  Any other exception is a fault and
  propagates with its traceback.

Once the program has ``plan_layers``, a layer of shape keys alone is
the case of it whose dicts hold nothing else: the change that adds it
sends every configuration through it and deletes the ``ConvSpec``
route, with ``tests/test_configs.py``'s plan pins as its test.

Either way the program's side is this.  ``execute_network(plan, x,
weights)`` runs the plan, with ``x`` the ``(C, H, W)`` input of
``layers[0]`` and ``weights`` one tensor per layer in file order.  The
whole network is one jitted program whose module name contains
``_execute``, and its conv kernels are ``conv2d_offload_planned`` calls
in the file's layer order (``xplane.reduce`` and ``pred_error`` rely on
both).  ``plan.layers`` holds one layer per configuration layer, each
with a Def-3 ``gross_duration``.  A configuration whose layers carry
program keys names its own reference module, ``references/<name>.py``,
with ``make_forward(cfg, passes=None|3)`` as ``conv_chain`` has it.

Set-up then makes the weights and a pool of distinct input images on
the device from ``--seed``, calls the network once (compiling it, or
loading it from the compile cache under ``.jax_compile_cache/``) and
warms up the runner.  The window then drives
``repro.kernels.emit.execute_network`` directly, one call per request,
for ``--seconds``.  With ``--trace 1`` a second, traced window of at
most ``TRACE_SECONDS`` follows, and the per-layer metrics are printed in
place of the end-to-end ones.

Once the windows have closed, a sample of the window's outputs drawn
from the seed is compared with the configuration's plain float32
reference.  The numbers compared are printed with their limits as the
last lines of standard error and under ``checks`` in the result, the
last line of standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH_FILE = ROOT / "BENCHMARK.json"
WARMUP_REQUESTS = 64
TRACE_SECONDS = 2.0
CHECK_SAMPLE = 128
BREAKDOWN_ENTRIES = 10
UNSTACK = 256
EXIT_REFUSED = 2
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
#: The only keys of a configuration's layer that the harness reads.
SHAPE_KEYS = ("c_in", "h_in", "w_in", "n_kernels", "h_k", "w_k", "s_h",
              "s_w")
#: The program's counters logged over the timed window.
COUNTERS = ("executor/traces", "executor/emit_misses", "executor/emit_hits")
#: What ``plan_layers`` raises to refuse a configuration.
REFUSALS = (ValueError, NotImplementedError)


class BenchError(Exception):
    """The run cannot be made as asked; no result is printed."""


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` beside this file, as a fresh module."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


def load_cell(workload: str):
    """(benchmark, cell, configuration, traffic) for ``workload``."""
    bench = _read_json(BENCH_FILE)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in {BENCH_FILE.name}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = _read_json(ROOT / configs[cell["config"]]["file"])
    for k, layer in enumerate(cfg["layers"]):
        missing = [key for key in SHAPE_KEYS if key not in layer]
        if missing:
            raise BenchError(f"layer {k} of {cfg['name']} lacks the shape "
                             f"keys {missing}")
    traffic = _read_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, cfg, traffic


def select_metrics(bench: dict, cell: str, per_layer: bool) -> list[dict]:
    """The cell's end-to-end metrics, or its per-layer ones.

    A metric with ``workloads`` is the listed cells'.  An end-to-end
    metric without it is every cell's; a per-layer metric without it is
    every cell's that reports the end-to-end metric it ``moves``."""
    ends = [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]
    if not per_layer:
        return ends
    reported = {m["name"] for m in ends}
    return [m for m in bench["per_layer"]
            if ("workloads" in m and cell in m["workloads"])
            or ("workloads" not in m and m["moves"] in reported)]


def read_metrics(entries: list[dict], ctx: dict) -> dict:
    """Each metric's reducer applied to ``ctx``; a reducer that finds
    nothing to read returns None, and the metric is left out."""
    out = {}
    for m in entries:
        value = load_module("metrics", m["name"].split(".")[0]).reduce(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_info(chips: int, peaks: dict):
    """The first device and its row of ``peaks.json``, or BenchError."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX's first device is on "
                         f"platform {dev.platform!r} ({dev.device_kind})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devices)}")
    if dev.device_kind not in peaks["devices"]:
        raise BenchError(f"device kind {dev.device_kind!r} has no row in "
                         f"peaks.json")
    return dev, peaks["devices"][dev.device_kind]


def make_inputs(cfg: dict, n_images: int, seed: int):
    """(weights, images) on the device, from ``seed``.

    One jitted call draws the weights, (N, C_in, Hk, Wk) from
    N(0, 1/fan_in), and a pool of ``n_images`` distinct (C, H, W) images
    from N(0, 1).  The pool is then cut into one array per image,
    ``UNSTACK`` images to a call, so that a request finds its image in
    hand."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    dtype = jnp.dtype(cfg["dtype"])
    layers = cfg["layers"]
    c, h, w = (layers[0][k] for k in ("c_in", "h_in", "w_in"))
    if n_images % UNSTACK:
        raise BenchError(f"pool_images {n_images} is not a multiple of "
                         f"{UNSTACK}")
    seed %= 1 << 64
    key = jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))

    @jax.jit
    def generate(key):
        k_img, *k_w = jax.random.split(key, 1 + len(layers))
        pool = jax.random.normal(k_img, (n_images * c, h, w),
                                 jnp.float32).astype(dtype)
        weights = [
            (jax.random.normal(k, (lay["n_kernels"], lay["c_in"],
                                   lay["h_k"], lay["w_k"]), jnp.float32)
             / np.sqrt(lay["c_in"] * lay["h_k"] * lay["w_k"])).astype(dtype)
            for k, lay in zip(k_w, layers)]
        return pool, weights

    @jax.jit
    def unstack(pool, start):
        block = lax.dynamic_slice_in_dim(pool, start, UNSTACK * c)
        return lax.split(block, [c] * UNSTACK, axis=0)

    pool, weights = generate(key)
    images = [image for start in range(0, n_images * c, UNSTACK * c)
              for image in unstack(pool, start)]
    jax.block_until_ready((images, weights))
    return weights, images


class Sample:
    """A reservoir of (request index, output) pairs drawn from a seed."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = random.Random(seed)
        self.items: list = []

    def add(self, k: int, out) -> None:
        if len(self.items) < self.size:
            self.items.append((k, out))
        else:
            j = self.rng.randrange(k + 1)
            if j < self.size:
                self.items[j] = (k, out)


def max_rel_err(outs, refs) -> float | None:
    """The largest over images of max|out - ref| / max|ref|; None where
    the outputs have the wrong shape or are not all finite."""
    import numpy as np
    if outs.shape != refs.shape or not np.isfinite(outs).all():
        return None
    diff = np.abs(outs - refs).reshape(len(outs), -1).max(axis=1)
    scale = np.abs(refs).reshape(len(refs), -1).max(axis=1)
    return float((diff / scale).max())


def reference_forward(cfg: dict):
    """The configuration's reference as one function of (x, weights), or
    BenchError where the reference refuses the configuration."""
    module = load_module("references", cfg["reference"])
    try:
        return module.make_forward(cfg)
    except ValueError as e:
        raise BenchError(f"reference {cfg['reference']!r} refuses "
                         f"{cfg['name']}: {e}") from e


def check(cfg: dict, forward, sample: Sample, images: list,
          weights) -> dict:
    """The numbers compared, each with its limit; ``forward`` is the
    reference's."""
    import jax.numpy as jnp
    import numpy as np
    items = sorted(sample.items, key=lambda item: item[0])
    limit = cfg["limits"]["max_rel_err"]
    if not items:   # nothing finished in the window: nothing is shown
        return {"max_rel_err": {"value": None, "limit": limit}}
    xs = jnp.stack([images[k % len(images)] for k, _ in items])
    outs = np.stack([np.asarray(out, np.float32) for _, out in items])
    refs = np.asarray(forward(xs, weights), np.float32)
    return {"max_rel_err": {"value": max_rel_err(outs, refs),
                            "limit": limit}}


def passed(checks: dict) -> bool:
    """Every number compared was read and lies within its limit."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def trace_window(runner, call, images, traffic, log_dir: str,
                 **length) -> tuple[dict, str]:
    """Run a window (``seconds=`` or ``requests=``) under the profiler,
    writing its trace into ``log_dir``; (runner result, path of the
    ``.xplane.pb``)."""
    import jax
    import xplane
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            result = runner.run(call, images, traffic, **length)
    finally:
        jax.profiler.stop_trace()
    return result, xplane.find_xplane(log_dir)


def traced_window(runner, call, images, traffic, seconds: float,
                  n_layers: int, program_spans) -> tuple[dict, dict, dict]:
    """A traced window, its trace read once, reduced and deleted: (runner
    result, ``xplane.reduce``'s reduction, ``spans.reduce``'s over the
    runner's and the program's spans)."""
    import spans
    import xplane
    runner_spans = getattr(runner, "SPANS", ())
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        result, path = trace_window(runner, call, images, traffic, log_dir,
                                    seconds=seconds)
        events = xplane.events(path)
        reduced = xplane.reduce(events, n_layers=n_layers)
        by_span = spans.reduce(events, program_spans=program_spans,
                               span_names=runner_spans)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return result, reduced, by_span


def open_device(cell: dict):
    """Point JAX's caches into the checkout, start JAX, and return
    (device, its peaks) or raise BenchError."""
    peaks = _read_json(HERE / "peaks.json")
    # The compile cache lives in the checkout at a fixed path; the
    # program keeps its cache where this variable says.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_compile_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.pop("REPRO_PLAN_CACHE", None)    # plan from the config alone
    dev, peak = device_info(int(cell["chips"]), peaks)
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"the program is not in this checkout "
                         f"({ROOT / 'src' / 'repro'})")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    return dev, peak


class Cache:
    """Counts JAX's persistent compile cache lookups."""

    def __init__(self):
        import jax
        from repro.compile_cache import enable_compile_cache
        self.dir = enable_compile_cache()
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.hits += 1
        elif event == CACHE_MISS:
            self.misses += 1

    @property
    def lookups(self) -> int:
        return self.hits + self.misses


def plan_network(cfg: dict, emit):
    """The program's plan of ``cfg``'s layers, by the route its keys
    choose (module docstring), or BenchError where the program cannot
    plan a configuration that carries keys of its own."""
    from repro.core.cost_model import HardwareModel
    hw = HardwareModel(nbop_pe=int(cfg["budget"]["nbop_pe"]),
                       size_mem=int(cfg["budget"]["size_mem"]))
    layers = cfg["layers"]
    if all(set(layer) <= set(SHAPE_KEYS) for layer in layers):
        from repro.core.conv_spec import ConvSpec
        specs = [ConvSpec(**layer) for layer in layers]
        return emit.plan_emitable_network(specs, hw, name=cfg["name"],
                                          verify=True)
    plan_layers = getattr(emit, "plan_layers", None)
    if plan_layers is None:
        raise BenchError(f"{cfg['name']}'s layers carry keys besides the "
                         f"shape keys, and the program has no "
                         f"{emit.__name__}.plan_layers to plan them")
    try:
        # a copy, so that the program cannot change what the harness reads
        return plan_layers(copy.deepcopy(layers), hw, name=cfg["name"],
                           verify=True)
    except REFUSALS as e:
        raise BenchError(f"{emit.__name__}.plan_layers refuses "
                         f"{cfg['name']}: {type(e).__name__}: {e}") from e


def set_up(cfg: dict, traffic: dict, seed: int, log) -> dict:
    """Plan, make the inputs, compile and warm up; the pieces a window
    needs and the seconds each phase took.  BenchError where the program
    cannot plan the configuration."""
    from repro.kernels import emit

    cache = Cache()
    clock = time.perf_counter
    setup = {"import_s": clock() - T_START}

    t0 = clock()
    plan = plan_network(cfg, emit)
    setup["plan_s"] = clock() - t0

    t0 = clock()
    weights, images = make_inputs(cfg, int(traffic["pool_images"]), seed)
    setup["inputs_s"] = clock() - t0

    def call(x):
        return emit.execute_network(plan, x, weights)

    t0 = clock()
    hits, misses = cache.hits, cache.misses
    call(images[0]).block_until_ready()
    setup["compile_s"] = clock() - t0
    first = f"hits={cache.hits - hits} misses={cache.misses - misses}"

    runner = load_module("runners", traffic["runner"])
    t0 = clock()
    warm = runner.run(call, images, traffic, requests=WARMUP_REQUESTS)
    setup["warmup_s"] = clock() - t0
    setup["setup_s"] = clock() - T_START
    log("setup: " + " ".join(f"{k}={v!r}" for k, v in setup.items())
        + f" | compile cache {cache.dir}: first call {first}; all set-up "
        f"hits={cache.hits} misses={cache.misses}")
    return {"plan": plan, "weights": weights, "images": images,
            "call": call, "runner": runner, "setup": setup, "cache": cache,
            "warm": warm, "program_spans": getattr(emit, "SPANS", ())}


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        bench, cell, cfg, traffic = load_cell(args.workload)
        dev, peak = open_device(cell)
        s = set_up(cfg, traffic, args.seed, log)
        forward = reference_forward(cfg)
    except BenchError as e:
        log(f"chipbench: {e}")
        return EXIT_REFUSED
    from repro.obs.metrics import REGISTRY

    runner, call, images = s["runner"], s["call"], s["images"]
    sample = Sample(CHECK_SAMPLE, args.seed)
    lookups = s["cache"].lookups
    counted = [REGISTRY.get(name) for name in COUNTERS]
    window = runner.run(call, images, traffic, seconds=args.seconds,
                        on_output=sample.add)
    counted = [REGISTRY.get(name) - c for name, c in zip(COUNTERS, counted)]
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell["chips"]),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    log(f"window: {json.dumps(window)} compiles in window="
        f"{s['cache'].lookups - lookups}")
    log("window counters: " + " ".join(
        f"{name}={c:g}" for name, c in zip(COUNTERS, counted)))

    trace = by_span = breakdown = None
    failed = window["failed"] + s["warm"]["failed"]
    if args.trace:
        traced, trace, by_span = traced_window(
            runner, call, images, traffic, min(TRACE_SECONDS, args.seconds),
            len(cfg["layers"]), s["program_spans"])
        failed += traced["failed"]
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        log(f"traced window: {json.dumps(traced)} images in trace="
            f"{trace['images']} layer_s={trace['layer_s']}")
        log(f"traced spans: {json.dumps(by_span)}")
        # the device's idle time by the innermost span that covered it,
        # the program's spans inside the runner's
        idle = sorted(([n, v] for n, v in by_span["idle_by_span"].items()
                       if v > 0), key=lambda item: -item[1])
        breakdown = {"device_ops": trace["device_ops"],
                     "idle_gaps": idle[:BREAKDOWN_ENTRIES]}

    ctx = {"cfg": cfg, "peak": peak, "setup": s["setup"],
           "window": window, "trace": trace,
           "spans": None if by_span is None else by_span["spans"],
           "predicted": [lp.gross_duration for lp in s["plan"].layers]}
    metrics = read_metrics(select_metrics(bench, cell["name"],
                                          bool(args.trace)), ctx)

    weights = s["weights"]
    del s, call
    checks = check(cfg, forward, sample, images, weights)
    correct = failed == 0 and passed(checks)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
