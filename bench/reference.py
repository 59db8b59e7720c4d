"""Plain float32 reference of a dense decoder language model (Qwen2/Qwen3).

It follows the published description of the two families and imports
nothing of the program under test:

    x = embed[tokens]
    per layer:  h = rms(x) * ln_attn
                q, k, v = h Wq + bq, h Wk + bk, h Wv + bv   (bias: Qwen2)
                q, k = rms(q) * q_norm, rms(k) * k_norm      (qk-norm: Qwen3)
                q, k = rope(q), rope(k)                      (rotate-half)
                x = x + causal_softmax(q k^T / sqrt(hd)) v Wo  (GQA)
                h = rms(x) * ln_mlp
                x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rms(x) * ln_f) W_unembed                      (untied head)

Everything is float32 with ``jax.default_matmul_precision("highest")``;
the layers run one at a time (a scan over the stacked weights, each layer
cast to float32 as it is used), so all 36 layers of qwen3-4b fit one chip.

Weights are random and drawn from the run's seed by ``weight_rule``: the
leaves in sorted-path order, one key each from ``split(PRNGKey(seed), n)``,
``normal`` leaves as ``N(0, 1) / sqrt(shape[-2])`` and the embedding as
``0.02 * N(0, 1)``, drawn in float32 and cast to the dtype they are served
in.  The program under test draws its own weights from the same
seed by the same rule; ``bench/tests`` checks that the two agree bit for
bit.

``quant="fp8"`` is the control of the serving comparison: the same forward
with every weight matrix (per tensor) and every activation entering a
matmul (per row) rounded to float8 e4m3 with an amax scale.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: dict) -> dict:
    """Sizes of a configuration file (Hugging Face ``config.json`` keys)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "qk_norm": bool(cfg.get("qk_norm", False)),
            "bias": bool(cfg.get("attention_bias", False))}


def weight_rule(cfg: dict) -> list:
    """``(path, shape, init, scale)`` of every leaf, in draw order."""
    m = dims(cfg)
    L, d, H, Hkv, hd, ff, V = (m[k] for k in
                               ("L", "d", "H", "Hkv", "hd", "ff", "V"))
    attn = {"wq": ((L, d, H, hd), "normal"), "wk": ((L, d, Hkv, hd), "normal"),
            "wv": ((L, d, Hkv, hd), "normal"),
            "wo": ((L, H, hd, d), "normal")}
    if m["bias"]:
        attn.update({"bq": ((L, H, hd), "zeros"), "bk": ((L, Hkv, hd), "zeros"),
                     "bv": ((L, Hkv, hd), "zeros")})
    if m["qk_norm"]:
        attn.update({"q_norm": ((L, hd), "ones"), "k_norm": ((L, hd), "ones")})
    leaves = {("embed", "tok"): ((V, d), "scaled"),
              ("ln_f", "w"): ((d,), "ones"),
              ("stack", "ln_attn", "w"): ((L, d), "ones"),
              ("stack", "ln_mlp", "w"): ((L, d), "ones"),
              ("stack", "mlp", "w_gate"): ((L, d, ff), "normal"),
              ("stack", "mlp", "w_up"): ((L, d, ff), "normal"),
              ("stack", "mlp", "w_down"): ((L, ff, d), "normal"),
              ("unembed", "w"): ((d, V), "normal")}
    leaves.update({("stack", "attn", k): v for k, v in attn.items()})
    return [(p, s, init, 0.02 if init == "scaled" else 1.0)
            for p, (s, init) in sorted(leaves.items())]


def _draw(shape, init, scale, key, dtype):
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "scaled":
        return (scale * jax.random.normal(key, shape)).astype(dtype)
    std = scale / math.sqrt(max(shape[-2] if len(shape) >= 2 else shape[-1], 1))
    return (std * jax.random.normal(key, shape)).astype(dtype)


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """All weights from the seed, one jitted program per leaf (so no leaf
    holds a float32 copy beside the others); a flat dict keyed by path."""
    rule = weight_rule(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(rule))
    return {path: jax.jit(functools.partial(_draw, shape, init, scale,
                                            dtype=dtype))(k)
            for (path, shape, init, scale), k in zip(rule, keys)}


# -- the forward --------------------------------------------------------------
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs            # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(x, axes):
    """Round to float8 e4m3 with an amax scale over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(x.dtype) * s


def _hidden(m: dict, w: dict, tokens, quant=None):
    """Final normed hidden states [S, d] of one sequence, in float32."""
    qw = (lambda a: _fp8(a, None)) if quant == "fp8" else (lambda a: a)
    qa = ((lambda a, axes=(-1,): _fp8(a, axes)) if quant == "fp8"
          else (lambda a, axes=(-1,): a))
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = qw(w[("embed", "tok")].astype(jnp.float32))[tokens]
    stack = {p[1:]: v for p, v in w.items() if p[0] == "stack"}
    G = m["H"] // m["Hkv"]
    mask = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        p = {k: v.astype(jnp.float32) for k, v in p.items()}
        h = qa(_rms(x, p[("ln_attn", "w")], m["eps"]))
        q = jnp.einsum("sd,dhk->shk", h, qw(p[("attn", "wq")]))
        k = jnp.einsum("sd,dhk->shk", h, qw(p[("attn", "wk")]))
        v = jnp.einsum("sd,dhk->shk", h, qw(p[("attn", "wv")]))
        if m["bias"]:
            q, k, v = (q + p[("attn", "bq")], k + p[("attn", "bk")],
                       v + p[("attn", "bv")])
        if m["qk_norm"]:
            q = _rms(q, p[("attn", "q_norm")], m["eps"])
            k = _rms(k, p[("attn", "k_norm")], m["eps"])
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
        k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(m["hd"])
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("qhk,hkd->qd", qa(o, (-2, -1)),
                           qw(p[("attn", "wo")]))
        h = qa(_rms(x, p[("ln_mlp", "w")], m["eps"]))
        a = jax.nn.silu(h @ qw(p[("mlp", "w_gate")])) \
            * (h @ qw(p[("mlp", "w_up")]))
        return x + qa(a) @ qw(p[("mlp", "w_down")]), None

    x, _ = jax.lax.scan(layer, x, stack)
    return _rms(x, w[("ln_f", "w")].astype(jnp.float32), m["eps"])


@functools.partial(jax.jit, static_argnames=("m", "quant", "first"))
def _logits(m, w, tokens, *, quant=None, first=0):
    with jax.default_matmul_precision("highest"):
        h = _hidden(dict(m), w, tokens, quant)[first:]
        qw = (lambda a: _fp8(a, None)) if quant == "fp8" else (lambda a: a)
        qa = (lambda a: _fp8(a, (-1,))) if quant == "fp8" else (lambda a: a)
        return qa(h) @ qw(w[("unembed", "w")].astype(jnp.float32))


def logits(cfg: dict, w: dict, tokens, *, first: int = 0, quant=None):
    """[S - first, V] float32 logits of one sequence at positions
    ``first..S-1``."""
    m = tuple(sorted(dims(cfg).items()))
    return _logits(m, w, jnp.asarray(tokens, jnp.int32), quant=quant,
                   first=first)


def served_gaps(cfg: dict, w: dict, prompt, served, *, control: bool = False):
    """How far below the reference's best logit each served token lies.

    ``served[j]`` is the token served after ``prompt + served[:j]``.
    Returns the gaps of the served tokens, and with ``control`` also the
    gaps of the tokens that the fp8 control puts first at the same
    positions (the control's reading)."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    first = len(prompt) - 1
    ref = logits(cfg, w, seq, first=first)
    best = ref.max(-1)
    gap = np.asarray(best - jnp.take_along_axis(
        ref, jnp.asarray(served, jnp.int32)[:, None], -1)[:, 0])
    if not control:
        return gap, None
    top = jnp.argmax(logits(cfg, w, seq, first=first, quant="fp8"), -1)
    ctl = np.asarray(best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0])
    return gap, ctl
