"""Plain float32 reference of Moonlight-16B-A3B (the DeepSeek-V3 block) for
training, at one chip's expert share.

It follows the published description and imports nothing of the program
under test (the dense reference, ``bench/reference.py``, lends its RMSNorm,
RoPE, fp8 rounding, placement and AdamW):

    x = embed[tokens]
    per layer:  h = rms(x) * ln_attn                          (eps rms_norm_eps)
                q = h Wq                        [S, H, nope+rope], no q LoRA
                c | k_pe = h Wkv_a              [S, r] | [S, rope]
                c = rms(c) * kv_norm            (eps 1e-6: kv_a_layernorm)
                k_nope | v = c Wkv_b            [S, H, nope] | [S, H, v]
                q_pe, k_pe = rope(q[..., nope:]), rope(k_pe)   (one key, all heads)
                x = x + causal_softmax([q_nope|q_pe] [k_nope|k_pe]^T
                                       / sqrt(nope+rope)) v Wo
                h = rms(x) * ln_mlp
      dense:    x = x + (silu(h Wg) * (h Wu)) Wd            (first_k_dense_replace)
      MoE:      s = sigmoid(h R)                (float32, all E experts)
                top = the top k of s + bias     (noaux_tc, one group)
                w_e = s_e / sum_top(s) * routed_scaling_factor
                x = x + sum_{e in top, held} w_e SwiGLU_e(h) + SwiGLU_shared(h)
    logits = (rms(x) * ln_f) W_unembed                       (untied head)

The expert share: the configuration holds ``n_routed_experts`` experts
(ids ``expert_offset`` on) of the router's ``published.n_routed_experts``;
an assignment to an expert held elsewhere adds nothing, in the reference as
in the program.  Each held expert is computed on every token and weighted
by its routing weight (zero where not chosen): plain, and no sort.  RoPE
pairs dimension i with i + rope/2 (rotate-half), as the program does;
Hugging Face stores the rope columns interleaved and de-interleaves them
before the same rotation, a fixed permutation of random weights.
Attention runs in query blocks of ``Q_BLOCK`` rows over every key, each
block under ``jax.checkpoint``, so 8,192 positions fit.

Everything is float32 with ``jax.default_matmul_precision("highest")``;
each layer runs under ``jax.checkpoint``.  Weights are drawn from the seed
by Hugging Face's DeepseekV3 rule: every matrix and the embedding
N(0, initializer_range), norms ones, the correction bias zeros; leaves in
sorted-path order, one key each from ``split(PRNGKey(seed), n)``, keyed by
the program's own parameter paths.

``quant="fp8"`` is the control: every weight matrix (per tensor) and every
activation entering a matmul (per row) rounded to float8 e4m3, gradients
to e5m2 (``reference._fp8``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference import _fp8, _rms, _rope, adamw, spread  # noqa: F401

KV_NORM_EPS = 1e-6      # Hugging Face's DeepseekV3RMSNorm default
Q_BLOCK = 1024


def dims(cfg: dict) -> dict:
    """Sizes of a configuration file (Hugging Face ``config.json`` keys)."""
    pub = cfg.get("published", {})
    return {"L": cfg["num_hidden_layers"],
            "L_dense": cfg["first_k_dense_replace"],
            "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "ff": cfg["intermediate_size"],
            "eff": cfg["moe_intermediate_size"],
            "E": pub.get("n_routed_experts", cfg["n_routed_experts"]),
            "E_held": cfg["n_routed_experts"],
            "offset": cfg.get("expert_offset", 0),
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"],
            "scale": float(cfg["routed_scaling_factor"]),
            "V": cfg["vocab_size"], "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def plan_overrides(cfg: dict) -> dict:
    """The program's ``Plan`` overrides that set every size of the
    configuration file."""
    m = dims(cfg)
    return {"n_layers": m["L"], "first_dense": m["L_dense"], "d_model": m["d"],
            "n_heads": m["H"], "n_kv_heads": m["H"], "d_ff": m["ff"],
            "kv_lora_rank": m["r"], "qk_nope_dim": m["nope"],
            "qk_rope_dim": m["rope"], "v_head_dim": m["v"],
            "moe_d_ff": m["eff"], "n_experts": m["E"],
            "experts_held": m["E_held"], "expert_offset": m["offset"],
            "top_k": m["k"], "n_shared_experts": m["shared"],
            "routed_scale": m["scale"], "vocab": m["V"],
            "rope_theta": m["theta"], "norm_eps": m["eps"]}


def weight_rule(cfg: dict) -> list:
    """``(path, shape, init, std)`` of every leaf, in draw order (sorted
    paths), keyed as the program's parameters are."""
    m = dims(cfg)
    d, H, r, V = m["d"], m["H"], m["r"], m["V"]
    std = float(cfg["initializer_range"])

    def attn(n, pre):
        return {pre + ("ln_attn", "w"): ((n, d), "ones"),
                pre + ("ln_mlp", "w"): ((n, d), "ones"),
                pre + ("attn", "wq"): ((n, d, H, m["nope"] + m["rope"]),
                                       "normal"),
                pre + ("attn", "wkv_a"): ((n, d, r + m["rope"]), "normal"),
                pre + ("attn", "kv_norm"): ((n, r), "ones"),
                pre + ("attn", "wkv_b"): ((n, r, H, m["nope"] + m["v"]),
                                          "normal"),
                pre + ("attn", "wo"): ((n, H, m["v"], d), "normal")}

    def swiglu(n, pre, ff, lead=()):
        return {pre + ("w_gate",): ((n,) + lead + (d, ff), "normal"),
                pre + ("w_up",): ((n,) + lead + (d, ff), "normal"),
                pre + ("w_down",): ((n,) + lead + (ff, d), "normal")}

    nd, nm = m["L_dense"], m["L"] - m["L_dense"]
    leaves = {("embed", "tok"): ((V, d), "normal"),
              ("ln_f", "w"): ((d,), "ones"),
              ("unembed", "w"): ((d, V), "normal"),
              ("stack", "moe", "router"): ((nm, d, m["E"]), "normal"),
              ("stack", "moe", "router_bias"): ((nm, m["E"]), "zeros")}
    if nd:
        leaves.update(attn(nd, ("dense",)))
        leaves.update(swiglu(nd, ("dense", "mlp"), m["ff"]))
    leaves.update(attn(nm, ("stack",)))
    leaves.update(swiglu(nm, ("stack", "moe"), m["eff"], (m["E_held"],)))
    leaves.update(swiglu(nm, ("stack", "moe", "shared"),
                         m["shared"] * m["eff"]))
    return [(p, s, init, std) for p, (s, init) in sorted(leaves.items())]


def _draw(shape, init, std, key, dtype):
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    return (std * jax.random.normal(key, shape)).astype(dtype)


def iter_weights(cfg: dict, seed: int, dtype=jnp.float32, sharding=None):
    """``(path, array)`` of every leaf in draw order, each drawn by its own
    jitted program when it is reached; ``sharding(path, shape)`` places a
    leaf as it is born."""
    rule = weight_rule(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(rule))
    for (path, shape, init, std), k in zip(rule, keys):
        out = None if sharding is None else sharding(path, shape)
        yield path, jax.jit(functools.partial(_draw, shape, init, std,
                                              dtype=dtype),
                            out_shardings=out)(k)


def make_weights(cfg: dict, seed: int, dtype=jnp.float32,
                 sharding=None) -> dict:
    """All weights from the seed; a flat dict keyed by path."""
    return dict(iter_weights(cfg, seed, dtype, sharding))


# -- the forward --------------------------------------------------------------
def _quant(quant):
    if quant == "fp8":
        return (lambda a: _fp8(a, None)), (lambda a, axes=(-1,): _fp8(a, axes))
    return (lambda a: a), (lambda a, axes=(-1,): a)


def _attention(m, p, h, qw, qa):
    """Causal latent attention of one sequence, h [S, d] -> [S, d]."""
    S = h.shape[0]
    pos = jnp.arange(S)
    nope, r = m["nope"], m["r"]
    q = jnp.einsum("sd,dhk->shk", h, qw(p[("attn", "wq")]))
    kv = h @ qw(p[("attn", "wkv_a")])
    c = qa(_rms(kv[:, :r], p[("attn", "kv_norm")], KV_NORM_EPS))
    k_pe = _rope(kv[:, None, r:], pos, m["theta"])               # [S, 1, rope]
    kvb = jnp.einsum("sr,rhk->shk", c, qw(p[("attn", "wkv_b")]))
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (S, m["H"], m["rope"]))], -1)
    v = kvb[..., nope:]
    q = jnp.concatenate([q[..., :nope],
                         _rope(q[..., nope:], pos, m["theta"])], -1)
    scale = 1.0 / math.sqrt(nope + m["rope"])
    nb = S // min(Q_BLOCK, S)

    @jax.checkpoint
    def block(qb, qpos):                                     # [Qb, H, nope+rope]
        s = jnp.einsum("qhk,shk->hqs", qb, k) * scale
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        return jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(lambda a: block(*a),
                    (q.reshape(nb, S // nb, *q.shape[1:]),
                     pos.reshape(nb, S // nb)))
    o = o.reshape(S, m["H"], m["v"])
    return jnp.einsum("qhk,hkd->qd", qa(o, (-2, -1)), qw(p[("attn", "wo")]))


def _swiglu(h, wg, wu, wd, qa):
    return qa(jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def _moe(m, p, h, qw, qa):
    """The routed experts held here and the shared experts, h [S, d]."""
    s = jax.nn.sigmoid(h @ qw(p[("moe", "router")]))         # [S, E], float32
    _, top = jax.lax.top_k(s + p[("moe", "router_bias")], m["k"])
    chosen = jnp.sum(jax.nn.one_hot(top, m["E"], dtype=s.dtype), 1)  # [S, E]
    w = s * chosen
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * m["scale"]
    w = jax.lax.dynamic_slice_in_dim(w, m["offset"], m["E_held"], 1)

    @jax.checkpoint
    def expert(y, e):                    # one held expert on every row
        wg, wu, wd, we = e
        return y + we[:, None] * _swiglu(h, qw(wg), qw(wu), qw(wd), qa), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p[("moe", "w_gate")], p[("moe", "w_up")],
                         p[("moe", "w_down")], w.T))
    sh = lambda n: qw(p[("moe", "shared", n)])
    return y + _swiglu(h, sh("w_gate"), sh("w_up"), sh("w_down"), qa)


def _hidden(m: dict, w: dict, tokens, quant=None):
    """Final normed hidden states [S, d] of one sequence, in float32; each
    layer is recomputed in the backward pass."""
    qw, qa = _quant(quant)
    x = qw(w[("embed", "tok")])[tokens]

    def layer(kind):
        def body(x, p):
            h = qa(_rms(x, p[("ln_attn", "w")], m["eps"]))
            x = x + _attention(m, p, h, qw, qa)
            h = qa(_rms(x, p[("ln_mlp", "w")], m["eps"]))
            if kind == "dense":
                mlp = lambda n: qw(p[("mlp", n)])
                return x + _swiglu(h, mlp("w_gate"), mlp("w_up"),
                                   mlp("w_down"), qa), None
            return x + _moe(m, p, h, qw, qa), None
        return jax.checkpoint(body)

    for kind in ("dense", "stack"):
        stack = {p[1:]: v for p, v in w.items() if p[0] == kind}
        if stack:
            x, _ = jax.lax.scan(layer(kind), x, stack)
    return _rms(x, w[("ln_f", "w")], m["eps"])


@functools.partial(jax.jit, static_argnames=("m", "quant", "rows", "place"))
def _loss_and_grad(m, w, tokens, labels, *, quant=None, rows=1, place=()):
    m = dict(m)
    qw, qa = _quant(quant)
    # keep every gradient where its weight lives
    pin = (lambda t: {k: jax.lax.with_sharding_constraint(v, dict(place)[k])
                      for k, v in t.items()}) if place else (lambda t: t)

    def loss(w, tok, lab):                       # [rows, S] -> mean NLL
        h = jax.vmap(lambda t: _hidden(m, w, t, quant))(tok)
        lg = qa(h) @ qw(w[("unembed", "w")])
        lse = jax.scipy.special.logsumexp(lg, -1)
        ll = jnp.take_along_axis(lg, lab[..., None], -1)[..., 0]
        return jnp.mean(lse - ll)

    B = tokens.shape[0]
    k = B // rows
    with jax.default_matmul_precision("highest"):
        if k == 1:                               # one micro-batch: no carry
            l, g = jax.value_and_grad(loss)(w, tokens, labels)
            return l, pin(g)
        micro = lambda a: a.reshape((k, rows) + a.shape[1:])

        def acc(carry, mb):
            l, g = jax.value_and_grad(loss)(w, *mb)
            return (carry[0] + l, pin(jax.tree.map(jnp.add, carry[1], g))), None

        zero = (jnp.zeros((), jnp.float32),
                pin(jax.tree.map(jnp.zeros_like, w)))
        (l, g), _ = jax.lax.scan(acc, zero, (micro(tokens), micro(labels)))
    return l / k, pin(jax.tree.map(lambda a: a / k, g))


def loss_and_grad(cfg: dict, w: dict, batch: dict, quant=None,
                  rows: int = 1):
    """Mean next-token cross-entropy of ``batch`` (``tokens`` and
    ``labels``, [B, S]) and its gradient for every leaf of ``w`` (float32),
    summed over micro-batches of ``rows`` rows.  Each gradient is placed
    as its weight is."""
    m = tuple(sorted(dims(cfg).items()))
    place = tuple(sorted((k, v.sharding) for k, v in w.items()
                         if isinstance(v, jax.Array)
                         and len(v.sharding.device_set) > 1))
    return _loss_and_grad(m, w, jnp.asarray(batch["tokens"], jnp.int32),
                          jnp.asarray(batch["labels"], jnp.int32),
                          quant=quant, rows=rows,
                          place=place if len(place) == len(w) else ())


def layer_out(cfg: dict, w: dict, h, layer: int = 0):
    """The routed experts held here plus the shared experts of MoE layer
    ``layer`` on rows ``h`` [S, d], float32: what the program's MoE layer
    returns for the same share."""
    m = dims(cfg)
    p = {k[2:]: v[layer] for k, v in w.items() if k[:2] == ("stack", "moe")}
    p = {("moe",) + k: v for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        return _moe(m, p, jnp.asarray(h, jnp.float32), *_quant(None))


# -- the work ------------------------------------------------------------------
def matmul_weights(m: dict) -> dict:
    """Matmul weights a token passes through, by part: ``attn`` (one
    layer's MLA projections), ``dense`` (a leading layer's SwiGLU), ``moe``
    (a MoE layer's router and shared experts), ``expert`` (one routed
    expert, per assignment) and ``head``."""
    d, H = m["d"], m["H"]
    return {"attn": (d * H * (m["nope"] + m["rope"]) + d * (m["r"] + m["rope"])
                     + m["r"] * H * (m["nope"] + m["v"]) + H * m["v"] * d),
            "dense": 3 * d * m["ff"],
            "moe": d * m["E"] + 3 * d * m["shared"] * m["eff"],
            "expert": 3 * d * m["eff"],
            "head": d * m["V"]}


def n_params(m: dict) -> int:
    """Every weight held here: layers (norms, router bias and the held
    experts), final norm, embedding and untied head."""
    w = matmul_weights(m)
    nd, nm = m["L_dense"], m["L"] - m["L_dense"]
    norms = 2 * m["d"] + m["r"]
    return (m["L"] * (w["attn"] + norms) + nd * w["dense"]
            + nm * (w["moe"] + m["E"] + m["E_held"] * w["expert"])
            + m["d"] + 2 * m["V"] * m["d"])


def expert_flops(m: dict, assigned: int) -> int:
    """Flops of the held experts' grouped matmuls for ``assigned``
    (token, held expert) assignments, forward and backward."""
    return 6 * matmul_weights(m)["expert"] * assigned


def flops(m: dict, seqs: int, seq_len: int, assigned: int) -> int:
    """Flops of training steps over ``seqs`` sequences of ``seq_len``
    tokens whose MoE layers made ``assigned`` assignments to held experts
    (the program's ``moe_assigned``): 6 per matmul weight and token (the
    embedding is a lookup) plus causal attention three times over, each
    query-key pair 2 * (nope + rope) + 2 * v flops per head and layer.
    Recomputation is not counted."""
    w = matmul_weights(m)
    tokens = seqs * seq_len
    per_token = (m["L"] * w["attn"] + m["L_dense"] * w["dense"]
                 + (m["L"] - m["L_dense"]) * w["moe"] + w["head"])
    keys = seqs * seq_len * (seq_len + 1) // 2
    attn = m["L"] * m["H"] * (2 * (m["nope"] + m["rope"]) + 2 * m["v"]) * keys
    return 6 * per_token * tokens + expert_flops(m, assigned) + 3 * attn

