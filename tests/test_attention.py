"""Attention correctness: chunked (flash-shape) vs full oracle, decode path,
cache updates, GQA/windows/offsets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention


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
