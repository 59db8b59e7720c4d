"""State-space / recurrent mixers: Mamba-2 (SSD), mLSTM, sLSTM.

All three follow the same contract as attention: a *parallel/chunked* form
for training & prefill (sub-quadratic, O(L) memory in chunks) and a *step*
form for decode carrying an explicit recurrent state, so a decode step's
memory does not grow with the context.

Chunked algorithms are validated against direct sequential recurrences in
tests/test_ssm.py; the Pallas mamba2 chunk kernel mirrors the same block
structure on TPU.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.sharding import ParamSpec
from . import layers

NEG_INF = -1e30


def _segsum(a):
    """a: [..., L] log-decays -> [..., L, L] lower-tri cumulative sums:
    out[t, s] = sum_{r=s+1..t} a_r  (t >= s), -inf above the diagonal."""
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, diff, NEG_INF)


def causal_conv1d(x, w, b=None, *, cache=None):
    """Depthwise causal conv. x: [B, L, C]; w: [K, C].

    With ``cache`` [B, K-1, C] (decode), prepends it instead of zero pad and
    returns (y, new_cache).
    """
    K = w.shape[0]
    if cache is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = cache.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None].astype(x.dtype)
            for i in range(K))
    if b is not None:
        y = y + b.astype(x.dtype)
    new_cache = xp[:, -(K - 1):, :] if cache is not None else None
    return y, new_cache


# ===========================================================================
# Mamba-2 (SSD)
# ===========================================================================
def mamba2_specs(d: int, *, expand: int = 2, head_dim: int = 64,
                 state: int = 64, n_groups: int = 1, d_conv: int = 4) -> dict:
    d_in = expand * d
    H = d_in // head_dim
    conv_ch = d_in + 2 * n_groups * state
    return {
        "in_proj": ParamSpec((d, 2 * d_in + 2 * n_groups * state + H),
                             ("embed", "inner")),
        "conv_w": ParamSpec((d_conv, conv_ch), ("conv", "inner"), init="scaled",
                            scale=0.1),
        "conv_b": ParamSpec((conv_ch,), ("inner",), init="zeros"),
        "A_log": ParamSpec((H,), ("heads",), init="zeros"),
        "D": ParamSpec((H,), ("heads",), init="ones"),
        "dt_bias": ParamSpec((H,), ("heads",), init="zeros"),
        "norm_w": ParamSpec((d_in,), ("inner",), init="ones"),
        "out_proj": ParamSpec((d_in, d), ("inner", "embed")),
    }


def _mamba2_split(x, p, cfg):
    """Project and split into (z, xbc-conv inputs, dt)."""
    d_in = cfg.expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    gn = cfg.ssm_groups * cfg.ssm_state
    zxbcdt = x @ p["in_proj"].astype(x.dtype)
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + d_in + 2 * gn]
    dt = zxbcdt[..., -H:]
    return z, xbc, dt


def mamba2_chunked(x, p, cfg, *, chunk: int = 256, return_state: bool = False):
    """Training/prefill pass. x: [B, L, D] -> [B, L, D] (+ final state)."""
    B, L, D = x.shape
    d_in = cfg.expand * D
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    G = cfg.ssm_groups
    dt_f = x.dtype

    z, xbc, dt = _mamba2_split(x, p, cfg)
    xbc, _ = causal_conv1d(xbc, p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(B, L, H, P)
    Bm = xbc[..., d_in:d_in + G * N].reshape(B, L, G, N)
    Cm = xbc[..., d_in + G * N:].reshape(B, L, G, N)
    rep = H // G
    Bm = jnp.repeat(Bm, rep, axis=2)                # [B,L,H,N]
    Cm = jnp.repeat(Cm, rep, axis=2)

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))      # [B,L,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                  # [H]
    a = dt * A[None, None]                                        # log decay
    xdt = xs * dt.astype(dt_f)[..., None]                         # dt-weighted input

    chunk = min(chunk, L)
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk
    # [B,nc,c,...]
    ac = a.reshape(B, nc, chunk, H)
    xc = xdt.reshape(B, nc, chunk, H, P)
    Bc = Bm.reshape(B, nc, chunk, H, N)
    Cc = Cm.reshape(B, nc, chunk, H, N)

    # --- intra-chunk (diagonal blocks) ---
    Lmat = jnp.exp(_segsum(ac.transpose(0, 1, 3, 2)))             # [B,nc,H,c,c]
    CB = jnp.einsum("bnthe,bnshe->bnhts", Cc, Bc)
    y_diag = jnp.einsum("bnhts,bnhts,bnshp->bnthp",
                        CB.astype(jnp.float32), Lmat,
                        xc.astype(jnp.float32))

    # --- chunk-final states ---
    a_cs = jnp.cumsum(ac, axis=2)                                  # [B,nc,c,H]
    a_tot = a_cs[:, :, -1]                                         # [B,nc,H]
    decay_to_end = jnp.exp(a_tot[:, :, None] - a_cs)               # [B,nc,c,H]
    S_chunk = jnp.einsum("bnshe,bnsh,bnshp->bnhpe",
                         Bc.astype(jnp.float32), decay_to_end,
                         xc.astype(jnp.float32))                   # [B,nc,H,P,N]

    # --- inter-chunk recurrence over nc (sequential scan) ---
    def scan_fn(S_prev, inp):
        S_c, atot = inp                                            # [B,H,P,N],[B,H]
        S_new = S_prev * jnp.exp(atot)[..., None, None] + S_c
        return S_new, S_prev

    S0 = jnp.zeros((B, H, P, N), jnp.float32)
    S_final, S_before = jax.lax.scan(
        scan_fn, S0,
        (jnp.moveaxis(S_chunk, 1, 0), jnp.moveaxis(a_tot, 1, 0)))
    S_before = jnp.moveaxis(S_before, 0, 1)                        # [B,nc,H,P,N]

    # --- inter-chunk contribution ---
    decay_from_start = jnp.exp(a_cs)                               # [B,nc,c,H]
    y_off = jnp.einsum("bnthe,bnth,bnhpe->bnthp",
                       Cc.astype(jnp.float32), decay_from_start, S_before)

    y = (y_diag + y_off).reshape(B, L, H, P).astype(dt_f)
    y = y + xs * p["D"].astype(dt_f)[None, None, :, None]
    y = y.reshape(B, L, d_in)
    y = layers.rms_norm(y * jax.nn.silu(z), p["norm_w"])
    out = y @ p["out_proj"].astype(dt_f)
    if return_state:
        K = p["conv_w"].shape[0]
        conv_in = (x @ p["in_proj"].astype(dt_f))[..., d_in:2 * d_in + 2 * G * N]
        state = {"ssm": S_final, "conv": conv_in[:, -(K - 1):, :]}
        return out, state
    return out


def mamba2_init_state(batch: int, cfg, dtype=jnp.float32) -> dict:
    d_in = cfg.expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    conv_ch = d_in + 2 * cfg.ssm_groups * cfg.ssm_state
    return {
        "ssm": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_state),
                         jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_d_conv - 1, conv_ch), dtype),
    }


def mamba2_step(x, state, p, cfg):
    """Decode one token. x: [B, 1, D] -> (y [B,1,D], new state)."""
    B, _, D = x.shape
    d_in = cfg.expand * D
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    G = cfg.ssm_groups
    dt_f = x.dtype

    z, xbc, dt = _mamba2_split(x, p, cfg)
    xbc, conv_cache = causal_conv1d(xbc, p["conv_w"], p["conv_b"],
                                    cache=state["conv"])
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :d_in].reshape(B, H, P)
    Bm = jnp.repeat(xbc[..., d_in:d_in + G * N].reshape(B, G, N), H // G, 1)
    Cm = jnp.repeat(xbc[..., d_in + G * N:].reshape(B, G, N), H // G, 1)

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))       # [B,H]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(dt * A[None])                                  # [B,H]
    S = state["ssm"] * decay[..., None, None] + jnp.einsum(
        "bhp,bhe,bh->bhpe", xs.astype(jnp.float32), Bm.astype(jnp.float32), dt)
    y = jnp.einsum("bhpe,bhe->bhp", S, Cm.astype(jnp.float32)).astype(dt_f)
    y = y + xs * p["D"].astype(dt_f)[None, :, None]
    y = y.reshape(B, 1, d_in)
    y = layers.rms_norm(y * jax.nn.silu(z), p["norm_w"])
    return y @ p["out_proj"].astype(dt_f), {"ssm": S, "conv": conv_cache}


# ===========================================================================
# mLSTM (xLSTM) - matrix-memory gated linear recurrence
# ===========================================================================
def mlstm_specs(d: int, *, n_heads: int, expand: int = 2,
                d_conv: int = 4) -> dict:
    d_in = expand * d
    return {
        "up_proj": ParamSpec((d, 2 * d_in), ("embed", "inner")),
        "conv_w": ParamSpec((d_conv, d_in), ("conv", "inner"), init="scaled",
                            scale=0.1),
        "conv_b": ParamSpec((d_in,), ("inner",), init="zeros"),
        "wq": ParamSpec((d_in, d_in), ("inner", None)),
        "wk": ParamSpec((d_in, d_in), ("inner", None)),
        "wv": ParamSpec((d_in, d_in), ("inner", None)),
        "w_gates": ParamSpec((d_in, 2 * n_heads), ("inner", None), scale=0.3),
        "b_i": ParamSpec((n_heads,), ("heads",), init="zeros"),
        "b_f": ParamSpec((n_heads,), ("heads",), init="ones"),
        "skip": ParamSpec((d_in,), ("inner",), init="ones"),
        "norm_w": ParamSpec((d_in,), ("inner",), init="ones"),
        "down_proj": ParamSpec((d_in, d), ("inner", "embed")),
    }


def _mlstm_qkvif(x, p, cfg, conv_cache=None):
    d_in = cfg.expand * cfg.d_model
    H = cfg.n_heads
    P = d_in // H
    dt = x.dtype
    up = x @ p["up_proj"].astype(dt)
    xi, z = up[..., :d_in], up[..., d_in:]
    xc, new_cache = causal_conv1d(xi, p["conv_w"], p["conv_b"],
                                  cache=conv_cache)
    xc = jax.nn.silu(xc)
    B, L = x.shape[:2]
    q = (xc @ p["wq"].astype(dt)).reshape(B, L, H, P)
    k = (xc @ p["wk"].astype(dt)).reshape(B, L, H, P) / math.sqrt(P)
    v = (xi @ p["wv"].astype(dt)).reshape(B, L, H, P)
    gates = (xc @ p["w_gates"].astype(dt)).astype(jnp.float32)
    i_pre = gates[..., :H] + p["b_i"].astype(jnp.float32)
    f_pre = gates[..., H:] + p["b_f"].astype(jnp.float32)
    return q, k, v, i_pre, f_pre, xi, z, new_cache


def mlstm_chunked(x, p, cfg, *, chunk: int = 256, return_state: bool = False):
    """Stabilized chunkwise mLSTM. x: [B, L, D] -> [B, L, D].

    Carry across chunks: (C [B,H,P,N], n [B,H,N], m [B,H]) where m is the
    running log-stabilizer (xLSTM eq. 15-19 in chunk form).
    """
    B, L, D = x.shape
    d_in = cfg.expand * D
    H = cfg.n_heads
    P = d_in // H
    dt_f = x.dtype
    q, k, v, i_pre, f_pre, xi, z, _ = _mlstm_qkvif(x, p, cfg)

    chunk = min(chunk, L)
    assert L % chunk == 0
    nc = L // chunk
    log_f = jax.nn.log_sigmoid(f_pre)                    # [B,L,H]

    def reshape_c(t, extra=()):
        return t.reshape((B, nc, chunk) + extra)

    qc = q.reshape(B, nc, chunk, H, P)
    kc = k.reshape(B, nc, chunk, H, P)
    vc = v.reshape(B, nc, chunk, H, P)
    ic = reshape_c(i_pre, (H,))
    fc = reshape_c(log_f, (H,))

    g = jnp.cumsum(fc, axis=2)                           # [B,nc,c,H]
    g_tot = g[:, :, -1]                                  # [B,nc,H]

    def body(carry, inp):
        C, n, m = carry                                  # [B,H,P,P],[B,H,P],[B,H]
        qi, ki, vi, ii, gi, gt = inp
        # state-contribution log-weights at end of chunk
        w = gt[:, None] - gi + ii                        # [B,c,H]
        m_loc = w.max(axis=1)                            # [B,H]
        m_new = jnp.maximum(m + gt, m_loc)
        scale_old = jnp.exp(m + gt - m_new)              # [B,H]
        w_exp = jnp.exp(w - m_new[:, None])               # [B,c,H]
        C_new = C * scale_old[..., None, None] + jnp.einsum(
            "bch,bchp,bchn->bhpn", w_exp, ki.astype(jnp.float32),
            vi.astype(jnp.float32))
        n_new = n * scale_old[..., None] + jnp.einsum(
            "bch,bchp->bhp", w_exp, ki.astype(jnp.float32))

        # outputs: inter (old state) + intra (this chunk)
        u = gi[:, :, None, :] - gi[:, None, :, :] + ii[:, None, :, :]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        u = jnp.where(tri[None, :, :, None], u, NEG_INF)  # [B,t,s,H]
        m_intra = u.max(axis=2)                           # [B,t,H]
        m_out = jnp.maximum(m[:, None] + gi, m_intra)     # [B,t,H]
        w_inter = jnp.exp(m[:, None] + gi - m_out)        # [B,t,H]
        w_intra = jnp.exp(u - m_out[:, :, None])          # [B,t,s,H]
        qk = jnp.einsum("bthp,bshp->btsh", qi.astype(jnp.float32),
                        ki.astype(jnp.float32))
        h_intra = jnp.einsum("btsh,btsh,bshn->bthn", qk, w_intra,
                             vi.astype(jnp.float32))
        h_inter = jnp.einsum("bthp,bhpn->bthn", qi.astype(jnp.float32),
                             C) * w_inter[..., None]
        num = h_inter + h_intra
        den_inter = jnp.einsum("bthp,bhp->bth", qi.astype(jnp.float32), n) \
            * w_inter
        den_intra = jnp.einsum("btsh,btsh->bth", qk, w_intra)
        den = den_inter + den_intra
        denom = jnp.maximum(jnp.abs(den), jnp.exp(-m_out))
        h = num / denom[..., None]
        return (C_new, n_new, m_new), h.astype(dt_f)

    C0 = jnp.zeros((B, H, P, P), jnp.float32)
    n0 = jnp.zeros((B, H, P), jnp.float32)
    m0 = jnp.full((B, H), 0.0, jnp.float32)
    inputs = tuple(jnp.moveaxis(t, 1, 0) for t in (qc, kc, vc, ic, g, g_tot))
    (Cf, nf, mf), hs = jax.lax.scan(body, (C0, n0, m0), inputs)
    h = jnp.moveaxis(hs, 0, 1).reshape(B, L, d_in)

    h = h + xi * p["skip"].astype(dt_f)
    h = h * jax.nn.silu(z)
    h = layers.rms_norm(h, p["norm_w"])
    out = h @ p["down_proj"].astype(dt_f)
    if return_state:
        K = p["conv_w"].shape[0]
        conv_in = (x @ p["up_proj"].astype(dt_f))[..., :d_in]
        state = {"C": Cf, "n": nf, "m": mf, "conv": conv_in[:, -(K - 1):, :]}
        return out, state
    return out


def mlstm_init_state(batch: int, cfg, dtype=jnp.float32) -> dict:
    d_in = cfg.expand * cfg.d_model
    H = cfg.n_heads
    P = d_in // H
    return {
        "C": jnp.zeros((batch, H, P, P), jnp.float32),
        "n": jnp.zeros((batch, H, P), jnp.float32),
        "m": jnp.zeros((batch, H), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_d_conv - 1, d_in), dtype),
    }


def mlstm_step(x, state, p, cfg):
    """Decode one token: exact recurrent form."""
    B, _, D = x.shape
    d_in = cfg.expand * D
    H = cfg.n_heads
    dt_f = x.dtype
    q, k, v, i_pre, f_pre, xi, z, conv_cache = _mlstm_qkvif(
        x, p, cfg, conv_cache=state["conv"])
    q, k, v = (t[:, 0].astype(jnp.float32) for t in (q, k, v))   # [B,H,P]
    ii = i_pre[:, 0]
    lf = jax.nn.log_sigmoid(f_pre[:, 0])                          # [B,H]
    m_new = jnp.maximum(lf + state["m"], ii)
    sf = jnp.exp(lf + state["m"] - m_new)
    si = jnp.exp(ii - m_new)
    C = state["C"] * sf[..., None, None] + si[..., None, None] * (
        k[..., :, None] * v[..., None, :])
    n = state["n"] * sf[..., None] + si[..., None] * k
    num = jnp.einsum("bhp,bhpn->bhn", q, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhp,bhp->bh", q, n)),
                      jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(B, 1, d_in).astype(dt_f)
    h = h + xi * p["skip"].astype(dt_f)
    h = h * jax.nn.silu(z)
    h = layers.rms_norm(h, p["norm_w"])
    y = h @ p["down_proj"].astype(dt_f)
    return y, {"C": C, "n": n, "m": m_new, "conv": conv_cache}


# ===========================================================================
# sLSTM - scalar-memory LSTM with exponential gating (sequential)
# ===========================================================================
def slstm_specs(d: int, *, n_heads: int) -> dict:
    P = d // n_heads
    return {
        "w_in": ParamSpec((d, 4 * d), ("embed", "inner")),
        "r": ParamSpec((n_heads, P, 4 * P), ("heads", "head_dim", None),
                       scale=0.5),
        "b": ParamSpec((4 * d,), ("inner",), init="zeros"),
        "norm_w": ParamSpec((d,), ("embed",), init="ones"),
        "up": ParamSpec((d, 2 * d), ("embed", "d_ff")),
        "down": ParamSpec((d, d), ("d_ff", "embed")),
    }


def _slstm_cell(x_t, h_prev, state, p, H, P):
    """One step. x_t: [B, 4d] preactivations from input; h_prev [B,H,P]."""
    c, n, m = state
    rec = jnp.einsum("bhp,hpq->bhq", h_prev, p["r"].astype(h_prev.dtype))
    pre = x_t.reshape(x_t.shape[0], H, 4 * P) + rec
    zi, ii, fi, oi = jnp.split(pre.astype(jnp.float32), 4, axis=-1)
    zt = jnp.tanh(zi)
    it = ii.mean(-1)                       # scalar gates per head
    ft = fi.mean(-1)
    ot = jax.nn.sigmoid(oi)
    m_new = jnp.maximum(ft + m, it)
    ig = jnp.exp(it - m_new)[..., None]
    fg = jnp.exp(ft + m - m_new)[..., None]
    c_new = fg * c + ig * zt
    n_new = fg * n + ig
    h_new = ot * (c_new / jnp.maximum(n_new, 1e-6))
    return h_new, (c_new, n_new, m_new)


def slstm_apply(x, p, cfg, *, return_state: bool = False):
    """x: [B, L, D]; sequential scan over L (no parallel form exists)."""
    B, L, D = x.shape
    H = cfg.n_heads
    P = D // H
    dt_f = x.dtype
    pre = x @ p["w_in"].astype(dt_f) + p["b"].astype(dt_f)

    def step(carry, x_t):
        h_prev, state = carry
        h_new, state = _slstm_cell(x_t, h_prev, state, p, H, P)
        # carry stays f32; the stacked ys are emitted in compute dtype so
        # the per-step save is a thin DUS row, not a full-buffer convert
        # round-trip (see EXPERIMENTS.md §Perf xlstm iteration 2)
        return (h_new, state), h_new.astype(dt_f)

    h0 = jnp.zeros((B, H, P), jnp.float32)
    st0 = (jnp.zeros((B, H, P), jnp.float32),
           jnp.zeros((B, H, P), jnp.float32),
           jnp.zeros((B, H), jnp.float32))
    (hf, stf), hs = jax.lax.scan(step, (h0, st0),
                                      jnp.moveaxis(pre, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, L, D)
    h = layers.rms_norm(h, p["norm_w"])
    u = h @ p["up"].astype(dt_f)
    u = jax.nn.gelu(u[..., :D]) * u[..., D:]
    out = u @ p["down"].astype(dt_f)
    if return_state:
        c, n, m = stf
        return out, {"h": hf, "c": c, "n": n, "m": m}
    return out


def slstm_init_state(batch: int, cfg, dtype=jnp.float32) -> dict:
    H = cfg.n_heads
    P = cfg.d_model // H
    z = lambda *s: jnp.zeros(s, jnp.float32)
    return {"h": z(batch, H, P), "c": z(batch, H, P), "n": z(batch, H, P),
            "m": z(batch, H)}


def slstm_step(x, state, p, cfg):
    B, _, D = x.shape
    H = cfg.n_heads
    P = D // H
    dt_f = x.dtype
    pre = (x @ p["w_in"].astype(dt_f) + p["b"].astype(dt_f))[:, 0]
    h_new, (c, n, m) = _slstm_cell(
        pre, state["h"], (state["c"], state["n"], state["m"]), p, H, P)
    h = h_new.reshape(B, 1, D).astype(dt_f)
    h = layers.rms_norm(h, p["norm_w"])
    u = h @ p["up"].astype(dt_f)
    u = jax.nn.gelu(u[..., :D]) * u[..., D:]
    y = u @ p["down"].astype(dt_f)
    return y, {"h": h_new, "c": c, "n": n, "m": m}
