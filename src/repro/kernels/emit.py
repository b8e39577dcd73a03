"""Emit Pallas kernels from solved offloading plans.

The bridge between the planning stack and the kernels:
:func:`emit_layer_kernel` maps an S1 :class:`~repro.core.network_planner.
LayerPlan` onto :func:`~repro.kernels.conv2d_offload.
conv2d_offload_planned` — grid, ``t_run`` and sweep order are read off
the solved strategy via :meth:`GroupedStrategy.as_grid`, so the kernel's
grid steps are, by construction, the plan's Def-3 steps in order.

"By construction" is the claim; :mod:`repro.analysis.kerncheck` is the
proof: it statically re-derives the emitted kernel's per-step DMA
regions and checks them against the plan's I_slices (traffic
conservation), its VMEM occupancy against the budget the plan was
solved under, and its DMA pipeline for hazards.  ``emit`` therefore
refuses anything it cannot map *exactly*:

* S2 plans (kernel-group swapping — no kernel implements swapping yet);
* strategies that are not a uniform grid sweep (tiled/hilbert groups);
* "row"-order sweeps whose windows overlap across rows: at a row turn
  the kernel would re-fetch the full window, charging more traffic than
  the plan's eager-free I_slice accounting.

The emitted kernel implements the layer's *gross* schedule (every input
pixel from HBM, every output written back); inter-layer reuse savings
are a schedule-level accounting on top and do not change the kernel.
One departure is emitted all the same: where the stride exceeds the
kernel (a 1x1/2 projection), a step fetches the whole span of its run,
the skipped columns included, where the plan's I_slice holds only the
pixels its patches read; ``kerncheck`` reports the difference.

:func:`plan_layers` plans a network given as a list of layer dicts: the
shape keys of a ``ConvSpec`` and the graph keys of :data:`GRAPH_KEYS`
(which earlier layer a conv reads, a pool and zero padding on the way
in, a residual add and a ReLU on the way out), read into one
:class:`~repro.core.network_planner.Node` a layer.  A layer of shape
keys alone reads the previous one through the chain's glue, as
:func:`plan_emitable_network` plans run.

:func:`execute_network` runs a whole emitted plan as one jitted program
on the kernels' (H, W, C) layout, each conv reading the tensor its node
names; a plan without a graph is the chain of :func:`chain_graph` (2x2
max-pool where the next layer's input is smaller, then centred zero
padding, as :func:`glue` adapts one tensor).  :func:`reference_network`
is the plain f32 chain it is checked against.
A plan is emitted once per plan object: the first call with a plan
runs :func:`emit_layer_kernel` for each layer and keeps the result with
the graph, as the jitted program's one static argument (its hash taken
then, not on each call), keyed by the plan's identity (never its content, whose hash walks every
group), and every later call with the same object reads it back.  The
entry holds only a weak reference to the plan and is dropped when the
plan is freed; a plan that emission refuses is not kept and raises on
every call.  Each call writes its two host phases, the emission (or its
lookup) and the launch, as :data:`SPANS` into a JAX profiler trace when
one is recording.  In ``repro.obs.metrics.REGISTRY`` each call counts
one of ``executor/emit_hits`` and ``executor/emit_misses`` (the calls
that ran emission), and each trace of the jitted program counts
``executor/traces``, its residual adds ``executor/joins``, the pools
it emits ``executor/pools``, its conv layers' kernel taps
``executor/taps`` and the MXU dots a grid step of theirs issues
``executor/tap_dots`` (fewer than the taps where taps share a dot).
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.conv_spec import ConvSpec
from repro.core.cost_model import HardwareModel
from repro.core.network_planner import (
    LayerPlan, NetworkPlan, Node, plan_network)
from repro.core.solver import SolveResult
from repro.core.strategies import (
    GridMeta, GroupedStrategy, lower_bound, zigzag)
from repro.kernels import KernelShapeError, ref, resolve_interpret
from repro.kernels.conv2d_offload import (
    conv2d_offload_planned, dots_per_step, t_in_cols)


#: Host spans of :func:`execute_network`, in the order a call opens them:
#: emitting every layer's kernel (or reading them back), then launching
#: the jitted program.
SPANS = ("executor.emit", "executor.launch")


#: A layer dict's keys that make its ``ConvSpec``.
SHAPE_KEYS = tuple(f.name for f in dataclasses.fields(ConvSpec))
#: A layer dict's graph keys (see :func:`read_graph`).
GRAPH_KEYS = ("input", "pool", "pad", "add", "relu")


class KernelEmitError(ValueError):
    """The plan cannot be mapped onto an implemented kernel."""


class GraphError(ValueError):
    """A layer list whose graph the executor cannot run."""


def kernel_vmem_elements(spec: ConvSpec, t_run: int) -> int:
    """VMEM elements the emitted kernel occupies, in the plan's units.

    The checker's kern/vmem convention: the resident Λ block (constant
    index_map — Pallas keeps one copy), the window/delta scratch buffers
    ``conv2d_offload_planned`` allocates, and two output blocks (Pallas
    double-buffers blocks whose index_map moves), each counted in logical
    elements.  The kernel's tile-padded allocation is larger; it sizes
    only the kernel's ``vmem_limit_bytes``, so solver choices do not
    depend on the padding.
    """
    t_in = t_in_cols(t_run, spec.s_w, spec.w_k)
    nw = t_run * spec.s_w
    lam = spec.kernel_elements
    win = spec.c_in * spec.h_k * t_in
    col = spec.c_in * spec.h_k * nw
    row = spec.c_in * max(1, min(spec.s_h, spec.h_k)) * t_in
    out2 = 2 * spec.c_out * t_run
    return lam + win + col + row + out2


@dataclasses.dataclass(frozen=True)
class EmittedConv:
    """A LayerPlan compiled to a concrete Pallas kernel invocation."""

    spec: ConvSpec
    grid_meta: GridMeta
    layer_index: int
    vmem_elements: int

    @property
    def t_run(self) -> int:
        return self.grid_meta.t_run

    @property
    def order(self) -> str:
        return self.grid_meta.order

    def run(self, x: jax.Array, w: jax.Array, *,
            interpret: bool | None = None) -> jax.Array:
        """Execute the plan: x (C_in, H_in, W_in), w (N, C_in, Hk, Wk)
        -> (N, H_out, W_out).  Transposes to and from :meth:`run_hwc`."""
        out = self.run_hwc(jnp.transpose(x, (1, 2, 0)),
                           jnp.transpose(w, (2, 3, 1, 0)),
                           interpret=interpret)
        return jnp.transpose(out, (2, 0, 1))

    def run_hwc(self, x: jax.Array, w: jax.Array, *,
                interpret: bool | None = None) -> jax.Array:
        """Execute the plan on the kernel's layout: x (H_in, W_in, C_in),
        w (Hk, Wk, C_in, N) -> (H_out, W_out, N)."""
        spec = self.spec
        if x.shape != (spec.h_in, spec.w_in, spec.c_in):
            raise KernelShapeError(
                f"layer {self.layer_index}: input {x.shape} != plan spec "
                f"({spec.h_in}, {spec.w_in}, {spec.c_in})")
        if w.shape != (spec.h_k, spec.w_k, spec.c_in, spec.c_out):
            raise KernelShapeError(
                f"layer {self.layer_index}: kernels {w.shape} != plan "
                f"spec ({spec.h_k}, {spec.w_k}, {spec.c_in}, {spec.c_out})")
        return conv2d_offload_planned(
            x, w, t_run=self.t_run, s_h=spec.s_h, s_w=spec.s_w,
            order=self.order, interpret=interpret)


def emit_layer_kernel(lp: LayerPlan) -> EmittedConv:
    """Map an S1 LayerPlan onto ``conv2d_offload_planned``.

    Raises :class:`KernelEmitError` for plans no implemented kernel
    realises exactly (see module docstring).  The result's grid,
    ``t_run`` and order come from the solved strategy, so
    ``repro.analysis.kerncheck`` can verify contract equivalence
    statically before the kernel is ever run.
    """
    if lp.mode != "s1":
        raise KernelEmitError(
            f"layer {lp.index}: mode {lp.mode!r} (kernel-group swapping) "
            f"has no emitted kernel")
    strat = lp.strategy
    if not isinstance(strat, GroupedStrategy):
        raise KernelEmitError(
            f"layer {lp.index}: {type(strat).__name__} is not a grouped "
            f"S1 strategy")
    meta = strat.as_grid()
    if meta is None:
        raise KernelEmitError(
            f"layer {lp.index}: strategy {strat.name!r} is not a uniform "
            f"grid sweep — no kernel grid realises its group order")
    spec = lp.spec
    if meta.order == "row" and meta.w_out_tiles > 1 \
            and spec.h_k > spec.s_h:
        raise KernelEmitError(
            f"layer {lp.index}: row-order sweep with overlapping rows "
            f"(h_k={spec.h_k} > s_h={spec.s_h}) re-fetches the full "
            f"window at every row turn — kernel traffic would exceed "
            f"the plan's I_slice charge; solve with zigzag instead")
    return EmittedConv(spec=spec, grid_meta=meta, layer_index=lp.index,
                       vmem_elements=kernel_vmem_elements(spec,
                                                          meta.t_run))


# --------------------------------------------------------------------- #
# Emitable planning: restrict the solver to kernel-realisable strategies
# --------------------------------------------------------------------- #

def grid_solve(spec: ConvSpec, p: int, hw: HardwareModel, *,
               nb_data_reload: int = 2, time_limit: float = 10.0,
               polish_iters: int = 0, use_milp: bool = False,
               rng_seed: int = 0, polish_restarts: int = 0) -> SolveResult:
    """``plan_network`` solve_fn over *emitable* strategies only.

    Candidates are zigzag sweeps with every run length ``t`` dividing
    ``w_out`` and ``t <= p``; feasibility is the emitted kernel's actual
    VMEM occupancy (:func:`kernel_vmem_elements`), which upper-bounds
    the plan-level ``peak_footprint_elements``.  Polishing knobs are
    accepted (the shared solve_fn signature) and ignored — the candidate
    set is tiny and enumerated exactly.
    """
    del time_limit, polish_iters, use_milp, rng_seed, polish_restarts
    best: GroupedStrategy | None = None
    for t in range(1, min(p, spec.w_out) + 1):
        if spec.w_out % t:
            continue
        if hw.size_mem is not None and \
                kernel_vmem_elements(spec, t) > hw.size_mem:
            continue
        cand = zigzag(spec, t)
        if best is None or cand.objective(hw) < best.objective(hw):
            best = cand
    if best is None:
        raise ValueError(
            f"no emitable zigzag strategy fits size_mem={hw.size_mem} "
            f"for layer {spec.c_in}x{spec.h_in}x{spec.w_in}"
            f"->{spec.c_out}")
    obj = best.objective(hw)
    return SolveResult(
        strategy=best, objective=obj,
        lower_bound=lower_bound(spec, best.max_group_size(), hw),
        seed_objective=obj, milp_status="skipped", milp_objective=None,
        polish_objective=obj,
        reload_ok=best.max_reloads() <= nb_data_reload)


def plan_emitable_network(specs, hw: HardwareModel, *, name: str,
                          **kwargs) -> NetworkPlan:
    """``plan_network`` restricted to plans every layer of which
    ``emit_layer_kernel`` accepts.  Inter-layer reuse is disabled: the
    emitted kernels implement gross layer schedules, and the checker's
    traffic-conservation rule compares against exactly that."""
    return plan_network(specs, hw, name=name, allow_reuse=False,
                        solve_fn=grid_solve, **kwargs)


def plan_layers(layers: Sequence[dict], hw: HardwareModel, *, name: str,
                verify: bool = True) -> NetworkPlan:
    """Plan a network given as layer dicts, in file order.

    Each dict holds a ``ConvSpec``'s shape keys and any of
    :data:`GRAPH_KEYS` (:func:`read_graph`).  Every conv is planned by
    :func:`grid_solve` under ``hw`` with inter-layer reuse off, so each
    layer is planned alone and its activation goes through HBM; the
    plan keeps the graph, one ``plan.layers`` entry a dict.  A list of
    shape keys alone plans as :func:`plan_emitable_network` does on the
    same specs and runs the same program.  Refusals are ``ValueError``
    subclasses: :class:`GraphError` for the graph, the planner's own
    for a layer it cannot plan."""
    with jax.profiler.TraceAnnotation("planner.plan_layers"):
        specs, graph = read_graph(layers)
        return plan_emitable_network(specs, hw, name=name, verify=verify,
                                     graph=graph)


# --------------------------------------------------------------------- #
# Network graphs
# --------------------------------------------------------------------- #

def pooled_hw(pool: str, h: int, w: int) -> tuple[int, int]:
    """(H, W) of an (h, w) map after ``pool``; GraphError where the pool
    is unknown or the map cannot take it."""
    if pool == "max2x2":
        if h % 2 or w % 2:
            raise GraphError(f"cannot 2x2-pool a {h}x{w} map")
        return h // 2, w // 2
    if pool == "max3x3s2p1":
        return (h - 1) // 2 + 1, (w - 1) // 2 + 1
    if pool == "avg_global":
        return 1, 1
    raise GraphError(f"unknown pool {pool!r}; known: max2x2, max3x3s2p1, "
                     f"avg_global")


def _chain_node(k: int, shape: tuple[int, int, int], spec: ConvSpec) -> Node:
    """How :func:`glue` adapts an (H, W, C) ``shape`` to ``spec``'s
    input, as the node of layer ``k`` reading layer ``k - 1``."""
    h, w, c = shape
    if c != spec.c_in:
        raise KernelShapeError(
            f"{c} channels feed a layer that expects {spec.c_in}")
    pool = None
    if h > spec.h_in or w > spec.w_in:
        if h % 2 or w % 2:
            raise KernelShapeError(f"cannot 2x2-pool a {h}x{w} map")
        pool, h, w = "max2x2", h // 2, w // 2
    ph, pw = spec.h_in - h, spec.w_in - w
    if ph < 0 or pw < 0:
        raise KernelShapeError(
            f"a {h}x{w} map does not fit input {spec.h_in}x{spec.w_in}")
    return Node(source=k - 1, pool=pool,
                pad=((ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2)))


def chain_graph(specs: Sequence[ConvSpec]) -> tuple[Node, ...]:
    """The graph of a chain: each layer reads the one before it through
    :func:`glue`, and the first reads the input as it is."""
    nodes = [Node(source=-1)]
    for k in range(1, len(specs)):
        prev = specs[k - 1]
        nodes.append(_chain_node(k, (prev.h_out, prev.w_out, prev.c_out),
                                 specs[k]))
    return tuple(nodes)


def _index(k: int, layer: dict, key: str, default):
    v = layer.get(key, default)
    if isinstance(v, bool) or not isinstance(v, int):
        raise GraphError(f"layer {k}: {key!r} must be a layer index, "
                         f"not {v!r}")
    return v


def read_graph(layers: Sequence[dict]
               ) -> tuple[list[ConvSpec], tuple[Node, ...]]:
    """The specs and graph of a layer list; GraphError where it cannot
    run.

    Graph keys of layer k, each optional:

    * ``input``: the layer whose output the conv reads, -1 for the
      network's input (default: k - 1).  It must be an earlier layer.
    * ``pool``: ``"max2x2"``, ``"max3x3s2p1"`` (3x3 window, stride 2,
      one row and column of -inf on each side) or ``"avg_global"``,
      applied to that tensor.
    * ``pad``: zero rows and columns added on each side after pooling.
    * ``add``: an earlier layer whose output is added to the conv's; the
      shapes must match.
    * ``relu``: apply ReLU after the add.

    A layer with any of them must get, after pool and pad, exactly its
    own input shape.  A layer with shape keys alone reads layer k - 1
    through the chain's glue (:func:`glue`).  Layer 0 reads the
    network's input as it is: its ``c_in, h_in, w_in`` are the input's
    shape.  Any other key is refused."""
    if not layers:
        raise GraphError("no layers")
    specs: list[ConvSpec] = []
    nodes: list[Node] = []
    shapes: list[tuple[int, int, int]] = []     # each layer's output
    for k, layer in enumerate(layers):
        unknown = sorted(set(layer) - set(SHAPE_KEYS) - set(GRAPH_KEYS))
        if unknown:
            raise GraphError(f"layer {k}: unknown keys {unknown}")
        missing = [key for key in SHAPE_KEYS if key not in layer]
        if missing:
            raise GraphError(f"layer {k}: missing shape keys {missing}")
        spec = ConvSpec(**{key: layer[key] for key in SHAPE_KEYS})
        if k == 0:
            image = (spec.h_in, spec.w_in, spec.c_in)
        if not set(layer) & set(GRAPH_KEYS):
            node = (Node(source=-1) if k == 0
                    else _chain_node(k, shapes[-1], spec))
        else:
            node = _graph_node(k, layer, spec, shapes, image)
        specs.append(spec)
        nodes.append(node)
        shapes.append((spec.h_out, spec.w_out, spec.c_out))
    return specs, tuple(nodes)


def _graph_node(k: int, layer: dict, spec: ConvSpec,
                shapes: Sequence[tuple[int, int, int]],
                image: tuple[int, int, int]) -> Node:
    """Layer ``k``'s node from its graph keys (see :func:`read_graph`)."""
    source = _index(k, layer, "input", k - 1)
    if not -1 <= source < k:
        raise GraphError(f"layer {k} reads layer {source}, which is not "
                         f"an earlier layer")
    pool = layer.get("pool")
    pad = layer.get("pad", 0)
    if isinstance(pad, bool) or not isinstance(pad, int) or pad < 0:
        raise GraphError(f"layer {k}: pad must be a count of rows, not "
                         f"{pad!r}")
    relu = layer.get("relu", False)
    if not isinstance(relu, bool):
        raise GraphError(f"layer {k}: relu must be true or false")
    if k == 0 and (source != -1 or pool is not None or pad):
        raise GraphError("layer 0 reads the network's input as it is: "
                         "no other input, pool or pad")
    h, w, c = image if source < 0 else shapes[source]
    if pool is not None:
        h, w = pooled_hw(pool, h, w)
    if c != spec.c_in:
        raise GraphError(f"layer {k}: {c} channels feed a layer that "
                         f"expects {spec.c_in}")
    if (h + 2 * pad, w + 2 * pad) != (spec.h_in, spec.w_in):
        raise GraphError(
            f"layer {k}: a {h}x{w} map padded by {pad} is not its "
            f"{spec.h_in}x{spec.w_in} input")
    add = None
    if "add" in layer:
        add = _index(k, layer, "add", None)
        if not 0 <= add < k:
            raise GraphError(f"layer {k} adds layer {add}, which is not an "
                             f"earlier layer")
        out = (spec.h_out, spec.w_out, spec.c_out)
        if shapes[add] != out:
            raise GraphError(f"layer {k}: adds layer {add}'s "
                             f"{shapes[add]} to its own {out} output")
    return Node(source=source, pool=pool, pad=((pad, pad), (pad, pad)),
                add=add, relu=relu)


# --------------------------------------------------------------------- #
# Whole-network execution
# --------------------------------------------------------------------- #

def apply_pool(y: jax.Array, kind: str) -> jax.Array:
    """``y`` (H, W, C) pooled by ``kind`` (:func:`pooled_hw`)."""
    h, w, c = y.shape
    if kind == "max2x2":
        return y.reshape(h // 2, 2, w // 2, 2, c).max(axis=(1, 3))
    if kind == "max3x3s2p1":
        return lax.reduce_window(y, -jnp.inf, lax.max, (3, 3, 1), (2, 2, 1),
                                 ((1, 1), (1, 1), (0, 0)))
    if kind == "avg_global":
        return jnp.mean(y, axis=(0, 1), keepdims=True)
    raise GraphError(f"unknown pool {kind!r}")


def glue(y: jax.Array, spec: ConvSpec) -> jax.Array:
    """Adapt layer output ``y`` (H, W, C) to the next layer's ``spec``.

    The planner treats pooling and padding between layers as free; this
    is what they are on the executed path.  Where ``y`` is larger than
    the next input (a stride-2 stage), a 2x2 max-pool halves it; then it
    is zero-padded, centred, to ``spec.h_in`` x ``spec.w_in``."""
    node = _chain_node(1, y.shape, spec)
    if node.pool is not None:
        y = apply_pool(y, node.pool)
    return jnp.pad(y, (*node.pad, (0, 0)))


def execute_network(plan: NetworkPlan, x: jax.Array,
                    weights: Sequence[jax.Array], *,
                    interpret: bool | None = None) -> jax.Array:
    """Run every layer of ``plan`` through its emitted kernel, in order,
    as one jitted program.

    x is the first layer's (C_in, H_in, W_in) input and ``weights[l]``
    layer l's (N, C_in, Hk, Wk) kernels; returns the last layer's
    (N, H_out, W_out) output.  The (C, H, W) <-> (H, W, C) transposes
    happen once, at the network's edges.

    The layers are emitted on the first call with ``plan`` and kept,
    keyed by the plan object, until the plan is freed; every later call
    with the same object reads them back.  Each call counts one of
    ``executor/emit_hits`` and ``executor/emit_misses`` in
    ``repro.obs.metrics.REGISTRY``."""
    with jax.profiler.TraceAnnotation("executor.emit"):
        program = _emitted_program(plan)
    if len(weights) != len(program.layers):
        raise KernelShapeError(
            f"{len(weights)} weight tensors for {len(program.layers)} "
            f"layers")
    with jax.profiler.TraceAnnotation("executor.launch"):
        return _execute(x, tuple(weights), program=program,
                        interpret=resolve_interpret(interpret))


class _Program:
    """The static part of ``_execute``: a plan's emitted layers and graph.

    JAX hashes a static argument on every call to find its compiled
    program; this one's hash is taken once, when the plan is emitted,
    rather than walking every layer and node on each call.  Programs of
    equal content are equal, so equal plans share one compiled
    program."""

    __slots__ = ("layers", "graph", "_hash")

    def __init__(self, layers: tuple[EmittedConv, ...],
                 graph: tuple[Node, ...]):
        self.layers = layers
        self.graph = graph
        self._hash = hash((layers, graph))

    def __hash__(self) -> int:
        return self._hash

    def __eq__(self, other) -> bool:
        return self is other or (isinstance(other, _Program)
                                 and self.layers == other.layers
                                 and self.graph == other.graph)


#: ``id(plan)`` -> (weak reference to the plan, its emitted program), for
#: every live plan :func:`execute_network` has emitted.
_EMITTED: dict[int, tuple[weakref.ref, _Program]] = {}


def _emitted_program(plan: NetworkPlan) -> _Program:
    """``plan``'s emitted layers and graph, emitting them on the first
    call with this plan object."""
    # Lazy import, as in _execute.
    from repro.obs.metrics import REGISTRY
    key = id(plan)
    entry = _EMITTED.get(key)
    if entry is not None and entry[0]() is plan:
        REGISTRY.incr("executor/emit_hits")
        return entry[1]
    REGISTRY.incr("executor/emit_misses")
    layers = tuple(emit_layer_kernel(lp) for lp in plan.layers)
    graph = (plan.graph if plan.graph is not None
             else chain_graph([lp.spec for lp in plan.layers]))

    def forget(ref: weakref.ref) -> None:
        # The plan is being freed; its id may be reused after this.
        if _EMITTED.get(key, (None,))[0] is ref:
            del _EMITTED[key]

    program = _Program(layers, graph)
    _EMITTED[key] = (weakref.ref(plan, forget), program)
    return program


@functools.partial(jax.jit, static_argnames=("program", "interpret"))
def _execute(x, weights, *, program: _Program, interpret: bool
             ) -> jax.Array:
    # Runs only while JAX traces the program, i.e. on a jit-cache miss.
    # Lazy import: repro.obs imports this module (through kerncheck).
    from repro.obs.metrics import REGISTRY
    REGISTRY.incr("executor/traces")
    image = jnp.transpose(x, (1, 2, 0))
    outs: list[jax.Array] = []
    pooled: dict[tuple[int, str], jax.Array] = {}
    for k, (layer, node, w) in enumerate(zip(program.layers, program.graph,
                                             weights)):
        h = image if node.source < 0 else outs[node.source]
        if node.pool is not None:
            # one pool per tensor and kind, however many convs read it
            if (node.source, node.pool) not in pooled:
                pooled[node.source, node.pool] = apply_pool(h, node.pool)
                REGISTRY.incr("executor/pools")
            h = pooled[node.source, node.pool]
        if k and node.source != k - 1:
            # Pin the program order: a conv that does not read the one
            # before it runs after it, so the k-th conv kernel on the
            # device is layer k.
            h, _ = lax.optimization_barrier((h, outs[-1]))
        if node.pad != ((0, 0), (0, 0)):
            h = jnp.pad(h, (*node.pad, (0, 0)))
        spec = layer.spec
        REGISTRY.incr("executor/taps", spec.h_k * spec.w_k)
        REGISTRY.incr("executor/tap_dots", dots_per_step(
            spec.h_k, spec.w_k, spec.c_in, h.dtype))
        h = layer.run_hwc(h, jnp.transpose(w, (2, 3, 1, 0)),
                          interpret=interpret)
        if node.add is not None:
            h = h + outs[node.add]
            REGISTRY.incr("executor/joins")
        if node.relu:
            h = jnp.maximum(h, 0)
        outs.append(h)
    return jnp.transpose(outs[-1], (2, 0, 1))


def reference_network(specs: Sequence[ConvSpec], x: jax.Array,
                      weights: Sequence[jax.Array]) -> jax.Array:
    """The plain f32 ``jax.numpy`` chain :func:`execute_network` is
    checked against: ``ref.conv2d`` (full precision) per layer with the
    same :func:`glue` between layers."""
    h = jnp.asarray(x, jnp.float32)
    for k, (spec, w) in enumerate(zip(specs, weights)):
        if k:
            h = jnp.transpose(glue(jnp.transpose(h, (1, 2, 0)), spec),
                              (2, 0, 1))
        h = ref.conv2d(h, jnp.asarray(w, jnp.float32), spec.s_h, spec.s_w)
    return h
