"""GPipe-style pipeline parallelism over a mesh axis via shard_map.

The multi-pod mesh declares ``pod`` outermost; by default it extends data
parallelism, but for models whose layer stack exceeds one pod's HBM the
launcher can instead assign ``pod`` as the PIPELINE axis: each pod holds a
contiguous stage of layers and microbatches stream through with
``jax.lax.ppermute`` boundary handoffs.

Schedule: GPipe (fill–steady–drain).  For S stages and M microbatches the
bubble fraction is (S-1)/(M+S-1) — the launcher picks M ≥ 4·S.  Stage
weights live only on their stage's devices (enforced by shard_map's
in_specs), so HBM per pod is 1/S of the stack.

This module is deliberately self-contained (plain functions over a stacked
layer pytree) so it composes with ANY of the 10 block functions: the stage
body is the same scanned block used by the non-pipelined path.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stage_layers(params_stacked, n_stages: int):
    """Reshape a (L, ...) stacked layer tree to (S, L/S, ...)."""
    def r(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])
    return jax.tree.map(r, params_stacked)


def pipeline_forward(stage_params, x_microbatches, stage_ids,
                     block_fn: Callable, *, axis: str = "pod",
                     remat: bool = True):
    """Run microbatches through pipeline stages inside shard_map.

    ``stage_params``: (S, L/S, ...) tree sharded so each device along
    ``axis`` holds its own stage (leading dim 1 per device).
    ``x_microbatches``: (M, mb, S_len, d) activations, replicated along
    ``axis``.  ``stage_ids``: the (S,) iota sharded P(axis) — its (1,)
    per-device slice is this device's stage index
    (compat.axis_index_input).  Returns (M, mb, S_len, d) outputs (valid
    on the LAST stage; callers read them there).
    """
    n_stages = jax.lax.axis_size(axis)
    stage_id = stage_ids[0]
    m = x_microbatches.shape[0]

    # local stage params: shard_map gives us the (1, L/S, ...) slice
    local = jax.tree.map(lambda a: a[0], stage_params)

    f = jax.checkpoint(block_fn) if remat else block_fn

    def run_stage(h):
        def body(carry, lp):
            out, _ = f(lp, carry)
            return out, None
        out, _ = jax.lax.scan(body, h, local)
        return out

    n_ticks = m + n_stages - 1
    zero = jnp.zeros_like(x_microbatches[0])
    # ring handoff: stage i receives from stage i-1; stage 0 receives zeros
    ring = [(i, i + 1) for i in range(n_stages - 1)]

    # indexed schedule: O(one microbatch) work per tick — stage 0 reads
    # x[t], the last stage writes outputs[t-(S-1)] in place
    outputs0 = jnp.zeros_like(x_microbatches)

    def tick(state, t):
        inflight, outputs = state
        mb_idx = jnp.clip(t, 0, m - 1)
        inject = jax.lax.select(t < m, x_microbatches[mb_idx], zero)
        h_in = jnp.where(stage_id == 0, inject, inflight)
        h_out = run_stage(h_in)
        handoff = jax.lax.ppermute(h_out, axis, ring)
        emit_idx = t - (n_stages - 1)
        valid = jnp.logical_and(stage_id == n_stages - 1, emit_idx >= 0)
        outputs = jax.lax.cond(
            valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, h_out, jnp.clip(emit_idx, 0, m - 1), 0),
            lambda o: o, outputs)
        return (handoff, outputs), None

    (_, outputs), _ = jax.lax.scan(tick, (zero, outputs0),
                                   jnp.arange(n_ticks))
    # only the last stage emitted (zeros elsewhere): psum replicates its
    # outputs across the pipeline axis so out_specs=P() is truly replicated
    return jax.lax.psum(outputs, axis)


def make_pipelined_fwd(mesh: Mesh, block_fn: Callable, n_stages: int,
                       *, axis: str = "pod", remat: bool = True):
    """shard_map-wrapped pipeline forward.

    Returns ``fwd(stage_params, x_microbatches) -> outputs`` where
    stage_params' leading dim is sharded over ``axis`` and activations are
    replicated over ``axis`` (their batch/model sharding is inherited from
    inner constraints).
    """
    fwd = functools.partial(pipeline_forward, block_fn=block_fn, axis=axis,
                            remat=remat)
    in_specs = (P(axis), P(), P(axis))
    out_specs = P()
    # manualize ONLY the pipeline axis (axis_names): the stage body keeps
    # the other mesh axes in auto (GSPMD) mode, so Megatron TP / sequence
    # sharding inside the blocks composes with the pipeline (TP-inside-PP).
    from repro.parallel.compat import axis_index_input, shard_map
    mapped = shard_map(fwd, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False,
                       axis_names=frozenset({axis}))

    def run(stage_params, x_microbatches):
        return mapped(stage_params, x_microbatches,
                      axis_index_input(n_stages))
    return run


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipelined_loss_fn(cfg, mesh, *, n_stages: int, n_micro: int,
                      axis: str = "pod"):
    """Dense-family LM loss with the layer stack pipelined over ``axis``.

    Params use the standard tree EXCEPT ``blocks`` leaves carry a leading
    (n_stages, L/n_stages, ...) layout sharded P(axis) — each pod holds
    only its stage (1/S of the stack in HBM).  Embedding/head run on every
    stage (they are small and the last stage needs them); microbatches
    stream through GPipe-style.

    Returns ``loss_fn(params, batch)`` suitable for jit/grad — AD flows
    through the shard_map/ppermute schedule.
    """
    from repro.models import lm
    from repro.parallel import sharding as sh

    def block_fn(lp, h):
        # pod is manual inside the pipeline shard_map: constraints in the
        # block must not reference it (batch/cache rules include pod)
        with sh.exclude_axes(axis):
            return lm.dense_block(lp, h, cfg)

    fwd = make_pipelined_fwd(mesh, block_fn, n_stages, axis=axis)

    def loss_fn(params, batch):
        from repro.models import layers as L
        tokens, targets, mask = (batch["tokens"], batch["targets"],
                                 batch["mask"])
        x = lm._embed(params, tokens, cfg)                # (B,S,d)
        b = x.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        xm = x.reshape((n_micro, b // n_micro) + x.shape[1:])
        outs = fwd(params["blocks"], xm)                  # (M, mb, S, d)
        hidden = outs.reshape((b,) + outs.shape[2:])
        hidden = L.apply_norm(params, "final_norm", hidden, cfg.norm)
        return lm.lm_loss_from_hidden(params, hidden, targets, mask, cfg)

    return loss_fn


def pipeline_param_specs(model, n_stages: int):
    """Abstract params with blocks staged: (S, L/S, ...) leading dims."""
    import jax
    params = model.abstract_params()
    def restage(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return jax.ShapeDtypeStruct(
            (n_stages, l // n_stages) + a.shape[1:], a.dtype)
    params["blocks"] = jax.tree.map(restage, params["blocks"])
    return params
