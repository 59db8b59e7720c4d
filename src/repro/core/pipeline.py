"""GPipe-style pipeline parallelism over a mesh axis (paper R2).

The model's layer stack is split into S contiguous stages, one per rank of
the pipeline mesh axis.  Microbatches flow through a static schedule of
S + M - 1 ticks; at each tick every stage computes its resident microbatch
and hands the activation to the next stage with ``collective_permute``
(core.collectives.pipeline_shift).  The schedule is expressed as a
``lax.scan`` over ticks inside ``shard_map``, so reverse-mode autodiff
derives the backward pipeline automatically (ppermute transposes to the
reverse shift) - 1F1B-ish interleaving falls out of XLA's scheduler rather
than being hand-written, which is the paper's "constraint-based
synchronization" idea applied to pipelining.

Bubble fraction: (S - 1) / (M + S - 1) - reported by ``bubble_fraction`` and
validated in tests.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from . import collectives


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def gpipe_apply(stage_fn: Callable, stage_params, x_micro, *,
                axis: str = "stage"):
    """Run microbatches through the pipeline (call inside shard_map).

    stage_fn(params_for_stage, x) -> y     applied by every stage
    stage_params: this rank's stage parameters (already sharded by caller)
    x_micro: [M, mb, ...] microbatched inputs (replicated across stages;
             only stage 0 injects them)
    returns [M, mb, ...] outputs as produced by the last stage (replicated
    via the closing broadcast from the last stage).
    """
    S = lax.axis_size(axis)
    sid = lax.axis_index(axis)
    M = x_micro.shape[0]
    T = M + S - 1
    mb_shape = x_micro.shape[1:]

    def tick(carry, t):
        state, outs = carry           # state: activation resident here
        # stage 0 injects microbatch t (if any left)
        inject = jnp.where(t < M, t, 0)
        x_in = x_micro[inject]
        state = jnp.where(sid == 0, x_in, state)
        valid = (t - sid >= 0) & (t - sid < M)
        y = stage_fn(stage_params, state)
        y = jnp.where(valid, y, state)
        # last stage records its finished microbatch
        out_idx = jnp.where(t - (S - 1) >= 0, t - (S - 1), 0)
        done = (sid == S - 1) & (t - (S - 1) >= 0) & (t - (S - 1) < M)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(done, y, outs[out_idx]), out_idx, 0)
        # hand activations downstream
        state = collectives.pipeline_shift(y, axis)
        return (state, outs), None

    state0 = jnp.zeros(mb_shape, x_micro.dtype)
    outs0 = jnp.zeros((M,) + mb_shape, x_micro.dtype)
    (_, outs), _ = lax.scan(tick, (state0, outs0), jnp.arange(T))
    # broadcast the last stage's outputs to every rank (psum of one-hot)
    outs = lax.psum(jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)),
                    axis)
    return outs


def make_pipeline_fn(stage_fn: Callable, mesh, *, axis: str = "stage",
                     param_spec=None, out_replicated: bool = True):
    """Wrap gpipe_apply in shard_map. stage params enter sharded on dim 0
    (one slice per stage)."""
    from jax.sharding import PartitionSpec as P

    def body(stacked_params, x_micro):
        my = jax.tree.map(lambda p: p[0], stacked_params)  # local slice
        return gpipe_apply(stage_fn, my, x_micro, axis=axis)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(param_spec if param_spec is not None else P(axis), P()),
        out_specs=P(),
        axis_names={axis}, check_vma=False)
