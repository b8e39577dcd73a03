"""Distributed step functions: train_step / serve_prefill / serve_step.

These are the functions the dry-run lowers and the real launcher runs.
All sharding is explicit: params and optimizer state carry the ParamDef
PartitionSpecs, inputs the cell's batch specs; GSPMD materialises the
collective schedule that EXPERIMENTS.md §Roofline audits."""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import Axes, ShapeCell
from repro.models.registry import ModelApi
from repro.optim import adamw


def make_train_step(api: ModelApi, axes: Axes | None,
                    opt_cfg: adamw.AdamWConfig | None = None,
                    num_microbatches: int = 8):
    """Training step with microbatched gradient accumulation.

    The global batch is split into ``num_microbatches`` slices scanned
    sequentially: only one microbatch's remat stack is live at a time (the
    activation-memory lever) and gradients accumulate into a pytree pinned
    to the parameter sharding (ZeRO-style: no replicated f32 grads)."""
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    pspecs = api.param_specs(axes) if axes else None
    # grads/accumulators take the full ZeRO-1 sharding (data x model,
    # pod-extended on the multi-pod mesh) so reductions are reduce-scatters
    # — even when the weights themselves are data-replicated (small archs).
    gspecs = adamw.state_specs(api.zero1_specs(axes), axes)["m"] \
        if axes else None

    def _pin(grads):
        if gspecs is None:
            return grads
        return jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(g, s),
            grads, gspecs)

    def _n_batch_shards():
        if axes is None:
            return 1
        try:
            mesh = jax.sharding.get_abstract_mesh()
            if mesh is None or mesh.empty:
                return 1
            shape = dict(mesh.shape)
            n = shape.get(axes.data, 1)
            if axes.pod:
                n *= shape.get(axes.pod, 1)
            return n
        except Exception:
            return 1

    def train_step(params, opt_state, batch):
        b = jax.tree.leaves(batch)[0].shape[0]
        nshards = _n_batch_shards()
        # microbatch rows must stay divisible by the batch shards, or GSPMD
        # replicates the microbatch (observed on the multi-pod MoE cells).
        m = num_microbatches
        while m > 1 and (b % m != 0 or (b // m) % nshards != 0):
            m //= 2
        # strided split (row r -> microbatch r % m): every data shard
        # contributes rows to every microbatch, so the batch sharding is
        # preserved inside the accumulation scan.
        micro = jax.tree.map(
            lambda x: jnp.swapaxes(
                x.reshape((b // m, m) + x.shape[1:]), 0, 1), batch)
        if axes is not None:
            micro = jax.tree.map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, P(*((None, axes.batch) + (None,) * (x.ndim - 2)))),
                micro)

        def accum(carry, mb):
            gsum, lsum = carry
            loss, grads = jax.value_and_grad(
                lambda p: api.loss_fn(p, mb, axes))(params)
            gsum = _pin(jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), gsum, grads))
            return (gsum, lsum + loss), None

        gzero = _pin(jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        (gsum, lsum), _ = jax.lax.scan(
            accum, (gzero, jnp.float32(0)), micro)
        grads = jax.tree.map(lambda g: g / m, gsum)
        loss = lsum / m
        params, opt_state, gnorm = adamw.update(params, grads, opt_state,
                                                opt_cfg)
        return loss, gnorm, params, opt_state

    return train_step


def make_prefill_step(api: ModelApi, axes: Axes | None,
                      max_len: int | None = None):
    def serve_prefill(params, batch):
        return api.prefill_fn(params, batch, axes, max_len=max_len)

    return serve_prefill


def make_decode_step(api: ModelApi, axes: Axes | None):
    def serve_step(params, cache, tokens, pos):
        return api.decode_fn(params, cache, tokens, pos, axes)

    return serve_step


def jit_train_step(api: ModelApi, axes: Axes, cell: ShapeCell):
    """jit with explicit in/out shardings for the dry-run / launcher."""
    pspecs = api.param_specs(axes)
    ospecs = adamw.state_specs(api.zero1_specs(axes), axes)
    _, bspecs = api.input_specs(cell, axes)
    # MoE transients scale with tokens/microbatch: slice finer for them.
    micro = 16 if api.cfg.n_experts else 8
    fn = make_train_step(api, axes, num_microbatches=micro)
    return jax.jit(
        fn,
        in_shardings=(pspecs, ospecs, bspecs),
        out_shardings=(P(), P(), pspecs, ospecs),
        donate_argnums=(0, 1))


def jit_prefill_step(api: ModelApi, axes: Axes, cell: ShapeCell):
    from jax.sharding import PartitionSpec as P
    from repro.models.common import param_specs as _pspecs_of
    pspecs = api.param_specs(axes)
    _, bspecs = api.input_specs(cell, axes)
    fn = make_prefill_step(api, axes, max_len=cell.seq_len)
    # pin the returned cache to the decode-cell cache sharding — without
    # this the prefill output cache lands batch-sharded only (observed
    # 12 GB/device of unsharded MLA cache on deepseek prefill_32k).
    cache_specs = _pspecs_of(api.cache_defs(cell.global_batch, cell.seq_len,
                                            axes))
    logits_spec = P(axes.batch if cell.global_batch > 1 else None, None)
    return jax.jit(fn, in_shardings=(pspecs, bspecs),
                   out_shardings=(logits_spec, cache_specs))


def jit_decode_step(api: ModelApi, axes: Axes, cell: ShapeCell):
    pspecs = api.param_specs(axes, layout="decode")
    inputs, ispecs = api.input_specs(cell, axes)
    fn = make_decode_step(api, axes)
    return jax.jit(
        fn,
        in_shardings=(pspecs, ispecs["cache"], ispecs["tokens"],
                      ispecs["pos"]),
        donate_argnums=(1,))


def abstract_train_args(api: ModelApi, cell: ShapeCell,
                        axes: Axes | None = None):
    params = api.abstract_params(axes)
    opt = adamw.abstract_state(params)
    inputs, _ = api.input_specs(cell, axes)
    return params, opt, inputs


def abstract_serve_args(api: ModelApi, cell: ShapeCell,
                        axes: Axes | None = None):
    params = api.abstract_params(axes)
    inputs, _ = api.input_specs(cell, axes)
    if cell.kind == "prefill":
        return params, inputs
    return params, inputs["cache"], inputs["tokens"], inputs["pos"]
