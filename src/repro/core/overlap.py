"""Communication/computation overlap strategies for data parallelism (R3).

The gradient exchange + solver update, expressed *explicitly* inside the
framework (unified, R6) as code over manual data-parallel mesh axes:

  * ``horovod`` - the paper's Fig.-1 baseline: one all-reduce per gradient
    tensor, dense solver states.  No fusion; collective launch count equals
    the tensor count.
  * ``phylanx`` - the paper-faithful strategy: gradients coalesced into
    runtime-adaptively capped fusion buckets (R5), one asynchronous
    all-reduce per bucket; XLA's latency-hiding scheduler can start each
    bucket's collective as soon as its last gradient is produced.
  * ``zero1``   - beyond-paper: the same fusion buckets, but reduce-scattered
    so each rank owns (and keeps solver state for) 1/N of every bucket;
    updated shards are all-gathered back.  Wire bytes per step match
    all-reduce, solver memory drops by the DP degree.

All three run inside ``jax.shard_map(..., axis_names=dp_axes)`` bodies, so
the collectives here are real lax collectives the scheduler can overlap.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import fusion
from ..optim import optimizers as optim


def dp_axis_size(dp_axes) -> jax.Array:
    n = 1
    for a in dp_axes:
        n = n * lax.axis_size(a)
    return n


def exchange_horovod(grads, dp_axes):
    """Per-tensor blocking-style all-reduce mean (Fig. 1 baseline)."""
    return jax.tree.map(lambda g: lax.pmean(g, dp_axes), grads)


def exchange_phylanx(grads, dp_axes, bucket_bytes: int,
                     fuse_mask=None):
    """Fused-bucket asynchronous all-reduce mean (paper-faithful).

    fuse_mask: per-leaf bool tree - True for tensors safe to coalesce.
    Tensor-parallel-sharded gradients must NOT be flattened into shared
    buckets (ravel+concat of differently-sharded arrays forces the SPMD
    partitioner to all-gather them to replicated - measured at 253 GB/step
    wire on chameleon-34b, §Perf iteration A2).  Those go through per-tensor
    all-reduce, which partitions cleanly; the paper's tensor-fusion win is
    for the many SMALL (replicated) tensors anyway.
    """
    leaves, treedef = jax.tree.flatten(grads)
    if fuse_mask is None:
        mask = [True] * len(leaves)
    else:
        mask = jax.tree.leaves(fuse_mask)
    fusable = [g for g, m in zip(leaves, mask) if m]
    out = list(leaves)
    if fusable:
        plan = fusion.make_plan(fusable, bucket_bytes)
        bufs = [lax.pmean(b, dp_axes) for b in fusion.pack(fusable, plan)]
        fused_out = jax.tree.leaves(fusion.unpack(bufs, plan))
        it = iter(fused_out)
        out = [next(it) if m else g for g, m in zip(leaves, mask)]
    out = [g if m else lax.pmean(g, dp_axes)
           for g, m in zip(out, mask)]
    return jax.tree.unflatten(treedef, out)


def dense_update(grads, opt_state, params, oc, dp_axes, *,
                 strategy: str, bucket_bytes: int):
    if strategy == "horovod":
        grads = exchange_horovod(grads, dp_axes)
    else:
        grads = exchange_phylanx(grads, dp_axes, bucket_bytes)
    return optim.update(grads, opt_state, params, oc)


# ---------------------------------------------------------------------------
# ZeRO-1 (per-tensor): reduce-scatter grads along dim0 -> sharded solver ->
# all-gather updated params.  Per-tensor rather than flat-bucket, because
# flattening TP-sharded tensors into shared buckets de-shards them (§Perf
# iteration A2).  A leaf is scattered when its dim0 divides the dp degree
# and is not already claimed by the model axis; small/ragged leaves keep a
# dense (replicated) solver state - they are a tiny fraction of memory.
# ---------------------------------------------------------------------------
def zero1_scatter_mask(param_specs, mesh, rules, ndp: int,
                       min_size: int = 1 << 14):
    """Per-leaf bool tree: True -> solver state sharded over dp on dim0."""
    from .sharding import ParamSpec, spec_for

    def decide(s: ParamSpec) -> bool:
        if not s.shape or s.shape[0] % max(ndp, 1) or s.size < min_size:
            return False
        pspec = spec_for(mesh, rules, s.shape, s.dims)
        dim0_free = len(pspec) == 0 or pspec[0] is None
        return bool(dim0_free and ndp > 1)

    return jax.tree.map(decide, param_specs,
                        is_leaf=lambda x: hasattr(x, "dims"))


def zero1_init_state(param_specs, scatter_mask, ndp: int):
    """GLOBAL shapes (the step's in_specs shard dim0 over dp)."""
    from .sharding import ParamSpec

    def mk(s, sc):
        return jnp.zeros(s.shape, jnp.float32)

    zeros = jax.tree.map(mk, param_specs, scatter_mask,
                         is_leaf=lambda x: hasattr(x, "dims"))
    return {"count": jnp.zeros((), jnp.int32), "m": zeros,
            "v": jax.tree.map(jnp.zeros_like, zeros)}


def zero1_state_shard_specs(scatter_mask, dp_axes):
    """shard_map in_specs for the zero1 state (dim0 over dp when scattered)."""
    from jax.sharding import PartitionSpec as P
    axes = tuple(dp_axes)
    leaf = lambda sc: P(axes) if sc else P()
    per = jax.tree.map(leaf, scatter_mask)
    return {"count": P(), "m": per, "v": jax.tree.map(leaf, scatter_mask)}


def zero1_update(grads, opt_state, params, oc, dp_axes, scatter_mask):
    """Inside shard_map: grads/params replicated over dp; scattered m/v
    enter as local dim0 shards."""
    axes = tuple(dp_axes)
    ndp = dp_axis_size(dp_axes)
    count = opt_state["count"] + 1

    g_leaves, treedef = jax.tree.flatten(grads)
    p_leaves = jax.tree.leaves(params)
    m_leaves = jax.tree.leaves(opt_state["m"])
    v_leaves = jax.tree.leaves(opt_state["v"])
    mask = jax.tree.leaves(scatter_mask)

    # phase 1: reduce (scatter when possible) + exact global grad norm
    reduced = []
    sq_scattered = jnp.zeros((), jnp.float32)
    sq_dense = jnp.zeros((), jnp.float32)
    for g, sc in zip(g_leaves, mask):
        if sc:
            g_sh = lax.psum_scatter(g.astype(jnp.float32), axes,
                                    scatter_dimension=0, tiled=True) / ndp
            sq_scattered += jnp.sum(jnp.square(g_sh))
            reduced.append(g_sh)
        else:
            g_r = lax.pmean(g.astype(jnp.float32), axes)
            sq_dense += jnp.sum(jnp.square(g_r))
            reduced.append(g_r)
    gn = jnp.sqrt(lax.psum(sq_scattered, axes) + sq_dense)
    clip = jnp.minimum(1.0, oc.grad_clip / jnp.maximum(gn, 1e-9))

    # phase 2: solver on shards; all-gather updated params
    new_p, new_m, new_v = [], [], []
    for g_r, p, m, v, sc in zip(reduced, p_leaves, m_leaves, v_leaves, mask):
        if sc:
            shard = m.shape[0]
            rank = lax.axis_index(axes)
            p_sh = lax.dynamic_slice_in_dim(
                p.astype(jnp.float32), rank * shard, shard, axis=0)
            p2, m2, v2 = optim.zero1_shard_update(g_r, p_sh, m, v, count, oc,
                                                  clip)
            p2 = lax.all_gather(p2, axes, axis=0, tiled=True)
        else:
            p2, m2, v2 = optim.zero1_shard_update(
                g_r, p.astype(jnp.float32), m, v, count, oc, clip)
        new_p.append(p2.astype(p.dtype))
        new_m.append(m2)
        new_v.append(v2)
    params = jax.tree.unflatten(treedef, new_p)
    state = {"count": count, "m": jax.tree.unflatten(treedef, new_m),
             "v": jax.tree.unflatten(treedef, new_v)}
    return params, state, {"grad_norm": gn}
