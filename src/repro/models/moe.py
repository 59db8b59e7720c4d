"""Mixture-of-Experts: token-choice top-k routing, two dispatch engines, an
expert share and shared experts.

Routing (``scoring``):

  * ``softmax`` - probabilities over the experts; the top k, renormalised;
    a Switch-style load-balance loss.
  * ``sigmoid`` - DeepSeek-V3's ``noaux_tc`` with one group: each expert's
    score is sigmoid(logit); the top k are chosen by score plus a
    correction bias (``router_bias``, which only selects); the chosen
    experts' unbiased scores are normalised to sum 1 and scaled by
    ``routed_scale``.  No aux loss.

Dispatch (``dispatch``):

  * ``einsum`` - GShard/Switch-style one-hot dispatch matmuls with
    per-group capacity: overflowing tokens are dropped, underflow slots are
    zero-padded.  Partitions cleanly (experts on the "model" axis produce
    all-to-alls) but burns flops proportional to tokens*E*capacity*d.
  * ``sort`` - dropless: every (token, expert) assignment is sorted by
    expert, each projection is one grouped matmul over the experts this
    layer holds (``gmm``), and the results come back by the inverse
    permutation.  No capacity and no dropped assignment.

The expert share: the expert weights (``w_gate``/``w_up``/``w_down``) hold
``E_held`` experts, ids ``expert_offset .. expert_offset + E_held - 1`` of
the router's ``E``.  The router runs over all ``E`` and the weights are
normalised over all k chosen; assignments to experts held elsewhere add
nothing here (the chips that hold them do), so the output is this share's
part of the routed sum.  Shared experts (``p["shared"]``, one SwiGLU) are
what every share computes alike, and are added here.

Named scopes (for the device trace): ``moe.route``, ``moe.dispatch``,
``moe.experts``, ``moe.combine``, ``moe.shared``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.sharding import ParamSpec
from . import layers


def moe_specs(d: int, ff: int, n_experts: int, *, n_held: int = 0,
              shared_ff: int = 0, router_bias: bool = False) -> dict:
    """The router over ``n_experts``, ``n_held`` experts' weights (all
    when 0), the selection bias (sigmoid routing) and a shared SwiGLU of
    width ``shared_ff`` (none when 0)."""
    held = n_held or n_experts
    sp = {
        "router": ParamSpec((d, n_experts), ("embed", "experts"), scale=0.5),
        "w_gate": ParamSpec((held, d, ff), ("experts", "embed", "d_ff")),
        "w_up": ParamSpec((held, d, ff), ("experts", "embed", "d_ff")),
        "w_down": ParamSpec((held, ff, d), ("experts", "d_ff", "embed")),
    }
    if router_bias:
        sp["router_bias"] = ParamSpec((n_experts,), (None,), init="zeros")
    if shared_ff:
        sp["shared"] = layers.mlp_specs(d, shared_ff, "swiglu")
    return sp


def capacity(group_tokens: int, n_experts: int, top_k: int,
             factor: float = 1.25) -> int:
    c = int(math.ceil(group_tokens * top_k * factor / n_experts))
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for tiling friendliness


def router_probs(x, w_router, top_k: int):
    """Returns (weights [T,k], expert ids [T,k], aux load-balance loss)."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, top_k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e(f_e * p_e)
    E = w_router.shape[-1]
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(gate_i[..., 0], E, dtype=jnp.float32)
    ce = jnp.mean(one_hot, axis=0)
    aux = E * jnp.sum(me * ce)
    return gate_w, gate_i, aux


def route(x, p, top_k: int, scoring: str, routed_scale: float):
    """x [T, d] -> (weights [T,k] f32, expert ids [T,k], aux loss)."""
    if scoring == "softmax":
        w, ids, aux = router_probs(x, p["router"], top_k)
        return (w if routed_scale == 1.0 else w * routed_scale), ids, aux
    if scoring != "sigmoid":
        raise ValueError(f"unknown router scoring {scoring!r}")
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(
        scores + p["router_bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * routed_scale
    return w, ids, jnp.zeros((), jnp.float32)


def _expert_ffn(xin, p, dt):
    """xin: [E, C', d] -> [E, C', d] per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"].astype(dt)))
    h = h * jnp.einsum("ecd,edf->ecf", xin, p["w_up"].astype(dt))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dt))


# ---------------------------------------------------------------------------
# einsum (GShard) dispatch
# ---------------------------------------------------------------------------
def _dispatch_einsum(x, p, gate_w, gate_i, group_size: int,
                     cap_factor: float):
    """x: [T, d] (T a multiple of group_size)."""
    T, d = x.shape
    E = p["router"].shape[-1]
    top_k = gate_i.shape[-1]
    dt = x.dtype
    G = T // group_size
    xg = x.reshape(G, group_size, d)
    gate_w = gate_w.reshape(G, group_size, top_k)
    gate_i = gate_i.reshape(G, group_size, top_k)
    C = capacity(group_size, E, top_k, cap_factor)

    # position of each (token, k) within its expert's capacity buffer
    e_onehot = jax.nn.one_hot(gate_i, E, dtype=jnp.float32)      # [G,S,k,E]
    # rank among same-expert assignments in (token, k) order
    flat = e_onehot.reshape(G, group_size * top_k, E)
    ranks = jnp.cumsum(flat, axis=1) - flat                       # [G,S*k,E]
    pos = jnp.sum(ranks * flat, axis=-1).reshape(G, group_size, top_k)
    keep = (pos < C).astype(jnp.float32)
    gate_w = gate_w * keep

    pos_onehot = jax.nn.one_hot(pos, C, dtype=jnp.float32)        # [G,S,k,C]
    # combine[g,s,e,c] = sum_k gate_w * onehot(e) * onehot(c)
    combine = jnp.einsum("gske,gskc,gsk->gsec", e_onehot, pos_onehot, gate_w)
    dispatch = (combine > 0).astype(dt)
    xin = jnp.einsum("gsec,gsd->egcd", dispatch, xg)              # [E,G,C,d]
    xin = xin.reshape(E, G * C, d)
    yout = _expert_ffn(xin, p, dt).reshape(E, G, C, d)
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(dt), yout)
    return y.reshape(T, d)


# ---------------------------------------------------------------------------
# sort-based dropless dispatch over the held experts
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _permute(x, idx, inv):
    """Rows ``x[idx]``, where ``inv`` is the inverse permutation of
    ``idx``: the gradient is a gather by ``inv``, not a scatter-add."""
    return x[idx]


def _permute_fwd(x, idx, inv):
    return x[idx], (idx, inv)


def _permute_bwd(res, g):
    idx, inv = res
    return g[inv], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _assign(ids, offset: int, n_held: int):
    """Each assignment's group, [T*k]: its expert's index among the held
    ones, or ``n_held`` for an expert held elsewhere."""
    local = ids.reshape(-1) - offset
    return jnp.where((local >= 0) & (local < n_held), local,
                     n_held).astype(jnp.int32)


def gmm(lhs, rhs, sizes):
    """Grouped matmul: rows ``sizes[:g].sum() .. sizes[:g+1].sum()`` of
    ``lhs`` [M, K] times ``rhs[g]`` [K, N] for each of ``rhs``'s groups; the
    rows after the last group (``sizes[-1]`` of them) come out zero.

    XLA's ragged dot: in the moonlight-16b-a3b.train-8k step on a v5e it
    ran the held experts in 36 ms a step where the Pallas megablox ``gmm``
    took 110 ms, and needs 0.25 GB less of the chip (PERF.md)."""
    return jax.lax.ragged_dot(lhs, rhs, sizes[:rhs.shape[0]],
                              preferred_element_type=lhs.dtype)


def _dispatch_sort(x, p, gate_w, gate_i, offset: int):
    """x [T, d] -> (y [T, d], held assignments, largest held load)."""
    T, d = x.shape
    k = gate_i.shape[-1]
    n_held = p["w_gate"].shape[0]
    dt = x.dtype
    with jax.named_scope("moe.dispatch"):
        group = _assign(gate_i, offset, n_held)                   # [T*k]
        order = jnp.argsort(group, stable=True)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype),
            unique_indices=True)
        sizes = jnp.bincount(group, length=n_held + 1).astype(jnp.int32)
        xs = _permute(jnp.repeat(x, k, axis=0), order, inv)       # [T*k, d]
    with jax.named_scope("moe.experts"):
        # gate and up as one grouped matmul: one [T*k, 2 ff] product, and
        # in the backward one [T*k, d] input gradient where two would be
        # summed.  Without the barrier XLA moves the concatenation and
        # cast across the layer loop, and the moonlight-16b-a3b.train-8k
        # step needs 0.4 GB more of the chip (v5e compile)
        wg, wu, wd = jax.lax.optimization_barrier(
            (p["w_gate"], p["w_up"], p["w_down"]))
        ff = wg.shape[-1]
        gu = gmm(xs, jnp.concatenate([wg, wu], -1).astype(dt), sizes)
        ys = gmm(jax.nn.silu(gu[:, :ff]) * gu[:, ff:], wd.astype(dt), sizes)
    with jax.named_scope("moe.combine"):
        held = (group < n_held).reshape(T, k)
        w = jnp.where(held, gate_w, 0.0).astype(dt)
        y = jnp.einsum("tk,tkd->td", w,
                       _permute(ys, inv, order).reshape(T, k, d))
    return y, sizes[:n_held].sum(), sizes[:n_held].max()


def _shared(x, p):
    with jax.named_scope("moe.shared"):
        return layers.apply_mlp(x, p, "swiglu")


def apply_moe(x, p, *, top_k: int, group_size: int = 512,
              cap_factor: float = 1.25, dispatch: str = "einsum",
              scoring: str = "softmax", routed_scale: float = 1.0,
              expert_offset: int = 0):
    """x: [B, S, d] -> (y [B, S, d], stats).  ``stats["aux"]`` is the
    load-balance loss; the ``sort`` engine adds ``moe_assigned`` (this
    layer's assignments to held experts) and ``moe_max_load`` (the most
    on one held expert)."""
    B, S, d = x.shape
    flat = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        gate_w, gate_i, aux = route(flat, p, top_k, scoring, routed_scale)
    stats = {"aux": aux}
    if dispatch == "sort":
        y, stats["moe_assigned"], stats["moe_max_load"] = _dispatch_sort(
            flat, p, gate_w, gate_i, expert_offset)
    else:
        if p["w_gate"].shape[0] != p["router"].shape[-1]:
            raise ValueError("the einsum engine computes every expert; an "
                             "expert share needs dispatch='sort'")
        gs = min(group_size, flat.shape[0])
        with jax.named_scope("moe.experts"):
            y = _dispatch_einsum(flat, p, gate_w, gate_i, gs, cap_factor)
    if "shared" in p:
        y = y + _shared(flat, p["shared"])
    return y.reshape(B, S, d), stats
