"""Attention correctness: chunked (flash-shape) vs full oracle, decode path,
cache updates, GQA/windows/offsets; latent attention's fused kernel path
and the rule that chooses it."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import run_devices
from repro.models import attention, layers


@pytest.mark.parametrize("B,S,H,KV,hd,window,causal", [
    (2, 128, 4, 2, 32, None, True),
    (1, 256, 8, 8, 16, None, True),
    (2, 192, 4, 1, 32, None, True),
    (1, 256, 2, 2, 64, 64, True),
    (2, 128, 4, 4, 32, None, False),
])
def test_chunked_matches_full(B, S, H, KV, hd, window, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    full = attention.attend_full(q, k, v, causal=causal, window=window)
    for qc, kc in [(64, 64), (32, 64), (128, 32)]:
        ch = attention.attend_chunked(q, k, v, causal=causal, window=window,
                                      q_chunk=qc, kv_chunk=kc)
        np.testing.assert_allclose(np.asarray(ch), np.asarray(full),
                                   rtol=1e-4, atol=1e-5)


def test_chunked_matches_full_with_narrower_v():
    """Latent attention's shapes: q and k wider than v (192 and 128 per
    head in Moonlight)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 24))
    k = jax.random.normal(ks[1], (2, 128, 4, 24))
    v = jax.random.normal(ks[2], (2, 128, 4, 16))
    full = attention.attend_full(q, k, v)
    ch = attention.attend_chunked(q, k, v, q_chunk=32, kv_chunk=64)
    assert ch.shape == (2, 128, 4, 16)
    np.testing.assert_allclose(np.asarray(ch), np.asarray(full),
                               rtol=1e-4, atol=1e-5)


def test_chunked_gradients_match_full():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))

    def loss_full(q):
        return jnp.sum(attention.attend_full(q, k, v) ** 2)

    def loss_chunk(q):
        return jnp.sum(attention.attend_chunked(q, k, v, q_chunk=16,
                                                kv_chunk=16) ** 2)
    g1 = jax.grad(loss_full)(q)
    g2 = jax.grad(loss_chunk)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-3,
                               atol=1e-4)


def test_decode_attend_matches_full_row():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S, H, KV, hd = 2, 40, 4, 2, 16
    q_all = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    full = attention.attend_full(q_all, k, v, causal=True)
    pos = S - 1
    # cache longer than S: slots after pos must be masked out
    pad = 8
    kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    one = attention.decode_attend(q_all[:, -1:], kc, vc, pos)
    np.testing.assert_allclose(np.asarray(one[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-4, atol=1e-5)


def test_cache_update_writes_position():
    B, S, KV, hd = 2, 16, 2, 8
    kc = jnp.zeros((B, S, KV, hd))
    vc = jnp.zeros((B, S, KV, hd))
    k_new = jnp.ones((B, 1, KV, hd))
    v_new = 2 * jnp.ones((B, 1, KV, hd))
    kc2, vc2 = attention.cache_update(kc, vc, k_new, v_new, 5)
    assert float(kc2[0, 5].sum()) == KV * hd
    assert float(vc2[0, 5].sum()) == 2 * KV * hd
    assert float(kc2.sum()) == B * KV * hd  # only one row written


def test_fully_masked_rows_are_finite():
    # sliding window smaller than chunk: early rows see nothing in later blocks
    q = jnp.ones((1, 64, 2, 8))
    k = jnp.ones((1, 64, 2, 8))
    v = jnp.ones((1, 64, 2, 8))
    out = attention.attend_chunked(q, k, v, causal=True, window=4,
                                   q_chunk=16, kv_chunk=16)
    assert bool(jnp.isfinite(out).all())


def test_rope_rotation_properties():
    from repro.models import layers
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    r = layers.apply_rope(x, pos)
    # norm preserved per pair
    n1 = jnp.linalg.norm(x, axis=-1)
    n2 = jnp.linalg.norm(r, axis=-1)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-5)
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(r[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-6)
    # relative property: <rope(q,m), rope(k,n)> depends only on m-n
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, 16))
    def dot_at(m, n):
        qq = layers.apply_rope(q, jnp.array([[m]]))
        kk = layers.apply_rope(k, jnp.array([[n]]))
        return float(jnp.sum(qq * kk))
    assert abs(dot_at(3, 1) - dot_at(7, 5)) < 1e-4


# ---------------------------------------------------------------------------
# Latent attention's fused kernel path (kernels/ops.py causal_attention)
# ---------------------------------------------------------------------------
MLA_SCALE = 1 / math.sqrt(192)     # MLA's q.k width; not a power of two


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("S,tile,dtype", [
    (512, 256, jnp.float32),
    (1024, None, jnp.float32),
    (512, 256, jnp.bfloat16),
    (1024, None, jnp.bfloat16),
])
def test_causal_attention_kernel_matches_full(S, tile, dtype, monkeypatch):
    """The Pallas kernel in the interpreter at MLA's widths (q.k 128 + 64,
    v 128), causal, the softmax scale folded into q: its output and the
    gradients of q, k and v against ``attend_full``.  In bfloat16 both
    paths round; there the kernel's error against the float32 oracle is
    no larger than that of the chunked path it replaces."""
    from repro.kernels import ops
    B, H = 2, 2
    ks = jax.random.split(jax.random.PRNGKey(S), 4)
    q = jax.random.normal(ks[0], (B, S, H, 192)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, H, 192)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, H, 128)).astype(dtype)
    ct = jax.random.normal(ks[3], (B, S, H, 128)).astype(dtype)
    if tile is not None:
        monkeypatch.setattr(ops, "CAUSAL_BLOCKS", {
            n: b if isinstance(b, bool) else tile
            for n, b in ops.CAUSAL_BLOCKS.items()})
    t = lambda a: a.transpose(0, 2, 1, 3)

    def kernel(q, k, v):
        qs = (q.astype(jnp.float32) * MLA_SCALE).astype(dtype)
        return t(ops.causal_attention(t(qs), t(k), t(v), interpret=True))

    def full(q, k, v):
        return attention.attend_full(q, k, v, scale=MLA_SCALE)

    def chunked(q, k, v):
        return attention.attend_chunked(q, k, v, q_chunk=256, kv_chunk=256,
                                        scale=MLA_SCALE)

    def run(f, *args):
        o, vjp = jax.vjp(f, *args)
        return (o,) + vjp(ct.astype(o.dtype))

    got = run(kernel, q, k, v)
    if dtype == jnp.float32:
        for a, b in zip(got, run(full, q, k, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)
        return
    f32 = lambda a: a.astype(jnp.float32)
    oracle = run(full, f32(q), f32(k), f32(v))
    for a, c, o in zip(got, run(chunked, q, k, v), oracle):
        assert a.dtype == jnp.bfloat16
        assert _rel(a, o) <= _rel(c, o) < 1e-2


def _mla_cfg():
    import dataclasses
    from repro.configs import get_config
    return dataclasses.replace(get_config("moonlight-16b-a3b"), d_model=64,
                               n_heads=2, n_kv_heads=2, kv_lora_rank=32)


def _mla_weights(cfg, key):
    from repro.core.sharding import param_structs
    specs = attention.mla_specs(cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                                cfg.qk_nope_dim, cfg.qk_rope_dim,
                                cfg.v_head_dim)
    leaves, treedef = jax.tree.flatten(param_structs(specs))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        0.1 * jax.random.normal(k, s.shape) for k, s in zip(keys, leaves)])


def _parent_mla_layer(x, p, cfg):
    """``mla_layer`` as it was before the kernel path existed."""
    dt = x.dtype
    nope, r = cfg.qk_nope_dim, cfg.kv_lora_rank
    pos = jnp.arange(x.shape[1])[None, :]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    kv = x @ p["wkv_a"].astype(dt)
    c = layers.rms_norm(kv[..., :r], p["kv_norm"], attention.MLA_KV_NORM_EPS)
    k_pe = layers.apply_rope(kv[..., None, r:], pos, cfg.rope_theta)
    kvb = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"].astype(dt))
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], layers.apply_rope(q[..., nope:], pos, cfg.rope_theta)],
        -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1]
                                  + (k_pe.shape[-1],))], -1)
    o = attention.attend(q, k, v, causal=True, q_chunk=cfg.q_chunk,
                         kv_chunk=cfg.kv_chunk)
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"].astype(dt))


def test_mla_layer_off_the_tpu_is_the_chunked_path():
    """On the CPU ``mla_layer`` takes the chunked path: no kernel in its
    program, and its output bit for bit what it was before the kernel path
    (the reference checks of tests/test_moonlight.py cover that code)."""
    from repro.kernels import ops
    assert not ops.causal_attention_applies()
    cfg = _mla_cfg()
    p = _mla_weights(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4096, cfg.d_model))
    layer = jax.jit(lambda x, p: attention.mla_layer(x, p, cfg))
    assert "tpu_custom_call" not in layer.lower(x, p).as_text()
    np.testing.assert_array_equal(
        np.asarray(layer(x, p)),
        np.asarray(jax.jit(lambda x, p: _parent_mla_layer(x, p, cfg))(x, p)))


def test_mla_layer_partitioned_attention_is_the_chunked_path():
    """Where the backend is a TPU but an activation hook's mesh has more
    than one device, the attention is partitioned and ``mla_layer`` lowers
    to the chunked path; with a one-device mesh it takes the kernel."""
    out = run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.configs import get_config
        from repro.core.sharding import act_hook, default_rules
        from repro.kernels import ops
        from repro.models import attention
        ops._on_tpu = lambda: True
        cfg = get_config("moonlight-16b-a3b")
        d = np.array(jax.devices())
        x = jnp.zeros((2, 256, 64))
        p = {"wq": jnp.zeros((64, 16, 192)), "wkv_a": jnp.zeros((64, 576)),
             "kv_norm": jnp.ones((512,)),
             "wkv_b": jnp.zeros((512, 16, 256)),
             "wo": jnp.zeros((16, 128, 64))}
        with act_hook(Mesh(d[:1].reshape(1, 1), ("data", "model")),
                      default_rules()):
            one = ops.causal_attention_applies()
        with act_hook(Mesh(d.reshape(1, 2), ("data", "model")),
                      default_rules()):
            two = ops.causal_attention_applies()
            text = jax.jit(lambda x, p: attention.mla_layer(x, p, cfg)
                           ).lower(x, p).as_text()
        print(one, two, "tpu_custom_call" in text, "dot_general" in text)
    """, n_devices=2)
    assert out.split() == ["True", "False", "False", "True"]


def test_mla_layer_kernel_path_matches_chunked_path(monkeypatch):
    """The kernel path of ``mla_layer`` (q, k, v in [B, H, S, d], the
    scale folded into q, the kernel in the interpreter) against its
    chunked path, in float32: the output and every gradient."""
    import functools
    from repro.kernels import ops
    cfg = _mla_cfg()
    p = _mla_weights(cfg, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 256, cfg.d_model))
    ct = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def run():
        out, vjp = jax.vjp(lambda x, p: attention.mla_layer(x, p, cfg), x, p)
        return [out] + jax.tree.leaves(vjp(ct))

    want = run()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "causal_attention", functools.partial(
        ops.causal_attention, interpret=True))
    assert ops.causal_attention_applies()
    got = run()
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)
