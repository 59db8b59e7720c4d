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

``quant="fp8"`` is the control of both comparisons: the same forward with
every weight matrix (per tensor) and every activation entering a matmul
(per row) rounded to float8 e4m3 with an amax scale.  In a training step
the gradient of each rounded value is rounded to float8 e5m2 in the same
way, as an fp8 training step computes.

Training: ``loss_and_grad`` is next-token cross-entropy and its gradient
by ``jax.value_and_grad``, every layer under ``jax.checkpoint`` and the
gradient summed in float32 over micro-batches of rows; ``adamw`` is the
plain AdamW step with the gradient clipped by its global norm.

This module is the default reference of a configuration file; one that
names another (``"reference": "<module>"``) gets ``bench/<module>.py``,
which provides ``dims``, ``make_weights`` and ``plan_overrides`` too.
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


def plan_overrides(cfg: dict) -> dict:
    """The program's ``Plan`` overrides that set every size of the
    configuration file."""
    m = dims(cfg)
    return {"n_layers": m["L"], "d_model": m["d"], "n_heads": m["H"],
            "n_kv_heads": m["Hkv"], "head_dim": m["hd"], "d_ff": m["ff"],
            "vocab": m["V"], "rope_theta": m["theta"],
            "qk_norm": m["qk_norm"], "qkv_bias": m["bias"]}


def weight_rule(cfg: dict) -> list:
    """``(path, shape, init, scale)`` of every leaf, in draw order: the
    program's own initialisation, or with ``"weights":
    "initializer_range"`` in the configuration file, Hugging Face's."""
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
    if cfg.get("weights") == "initializer_range":
        # Hugging Face's own initialisation: every matrix and the embedding
        # N(0, initializer_range), norms ones, biases zeros
        std = float(cfg["initializer_range"])
        return [(p, s, "scaled" if init in ("normal", "scaled") else init,
                 std) for p, (s, init) in sorted(leaves.items())]
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


def iter_weights(cfg: dict, seed: int, dtype=jnp.bfloat16, sharding=None):
    """``(path, array)`` of every leaf in draw order, each drawn by its own
    jitted program when it is reached (so no leaf holds a float32 copy
    beside the others).  ``sharding(path, shape)`` places a leaf as it is
    born."""
    rule = weight_rule(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(rule))
    for (path, shape, init, scale), k in zip(rule, keys):
        out = None if sharding is None else sharding(path, shape)
        yield path, jax.jit(functools.partial(_draw, shape, init, scale,
                                              dtype=dtype),
                            out_shardings=out)(k)


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16,
                 sharding=None) -> dict:
    """All weights from the seed; a flat dict keyed by path."""
    return dict(iter_weights(cfg, seed, dtype, sharding))


def spread(devices) -> callable:
    """A ``sharding(path, shape)`` for ``make_weights`` that splits each
    leaf over ``devices`` along its largest axis that they divide
    (replicated where none does)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("x",))
    n = len(devices)

    def sharding(path, shape):
        axes = [i for i in np.argsort(shape)[::-1] if shape[i] % n == 0]
        spec = [None] * len(shape)
        if axes:
            spec[axes[0]] = "x"
        return NamedSharding(mesh, P(*spec))
    return sharding


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


def _round(x, axes, dtype):
    """Round to ``dtype`` (a float8) with an amax scale over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / float(
        jnp.finfo(dtype).max)
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(x.dtype) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fp8(x, axes):
    """Round to float8 e4m3 with an amax scale over ``axes``; in the
    backward pass the incoming gradient is rounded to float8 e5m2 with its
    own amax scale over the same axes (fp8 training's two formats)."""
    return _round(x, axes, jnp.float8_e4m3fn)


def _fp8_fwd(x, axes):
    return _fp8(x, axes), None


def _fp8_bwd(axes, _, g):
    return (_round(g, axes, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _hidden(m: dict, w: dict, tokens, quant=None, remat=False):
    """Final normed hidden states [S, d] of one sequence, in float32;
    ``remat`` recomputes each layer in the backward pass."""
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

    x, _ = jax.lax.scan(jax.checkpoint(layer) if remat else layer, x, stack)
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


# -- training -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("m", "quant", "rows", "place"))
def _loss_and_grad(m, w, tokens, labels, *, quant=None, rows=1, place=()):
    m = dict(m)
    qw = (lambda a: _fp8(a, None)) if quant == "fp8" else (lambda a: a)
    qa = (lambda a: _fp8(a, (-1,))) if quant == "fp8" else (lambda a: a)
    # keep every gradient where its weight lives
    pin = (lambda t: {k: jax.lax.with_sharding_constraint(v, dict(place)[k])
                      for k, v in t.items()}) if place else (lambda t: t)

    def loss(w, tok, lab):                       # [rows, S] -> mean NLL
        h = jax.vmap(lambda t: _hidden(m, w, t, quant, remat=True))(tok)
        lg = qa(h) @ qw(w[("unembed", "w")])
        lse = jax.scipy.special.logsumexp(lg, -1)
        ll = jnp.take_along_axis(lg, lab[..., None], -1)[..., 0]
        return jnp.mean(lse - ll)

    B = tokens.shape[0]
    micro = lambda a: a.reshape((B // rows, rows) + a.shape[1:])

    def acc(carry, mb):
        l, g = jax.value_and_grad(loss)(w, *mb)
        return (carry[0] + l, pin(jax.tree.map(jnp.add, carry[1], g))), None

    zero = (jnp.zeros((), jnp.float32), pin(jax.tree.map(jnp.zeros_like, w)))
    with jax.default_matmul_precision("highest"):
        (l, g), _ = jax.lax.scan(acc, zero, (micro(tokens), micro(labels)))
    k = B // rows
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


def global_norm(tree: dict):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in tree.values()))


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 2))
def _adamw(w, g, state, count, *, opt):
    o = dict(opt)
    gn = global_norm(g)
    scale = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gn, 1e-9))
    new_w, new_s = {}, {}
    for k in w:
        gk = g[k] * scale
        mk = o["b1"] * state[k][0] + (1 - o["b1"]) * gk
        vk = o["b2"] * state[k][1] + (1 - o["b2"]) * gk * gk
        mh = mk / (1 - o["b1"] ** count)
        vh = vk / (1 - o["b2"] ** count)
        new_w[k] = w[k] - o["lr"] * (mh / (jnp.sqrt(vh) + o["eps"])
                                     + o["weight_decay"] * w[k])
        new_s[k] = (mk, vk)
    return new_w, new_s, gn, jax.tree.map(lambda a: a * scale, g)


def adamw(w: dict, g: dict, state, count: int, opt: dict):
    """One AdamW step (``opt``: ``lr``, ``b1``, ``b2``, ``eps``,
    ``weight_decay``, ``grad_clip``) on the gradient clipped by its global
    norm; ``state`` None at the first step.  Returns the new weights and
    state, the global norm before clipping and the clipped gradient.
    ``w`` and ``state`` are donated."""
    if state is None:
        state = {k: (jnp.zeros_like(a), jnp.zeros_like(a))
                 for k, a in w.items()}
    return _adamw(w, g, state, jnp.float32(count),
                  opt=tuple(sorted(opt.items())))
