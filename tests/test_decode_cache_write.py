"""The decode step's KV-cache write: the dense/MoE layer scan carries the
stacked cache and writes one position per row into it in place.

Its reference is the loop it replaced, kept here: the cache passed through
the scan as xs/ys and written by a one-hot select over the sequence dim.
Both must give bit-identical caches and logits, a row at a position outside
the cache must write nothing, and every other position must be untouched.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.sharding import init_params
from repro.models import blocks, layers
from repro.models.model import build_model

B, S = 4, 32


def _onehot_decode_step(cfg, params, cache, tokens, pos):
    """The dense/MoE decode step before the cache rode in the carry."""
    cfg = dataclasses.replace(cfg, cache_update="masked")
    x = layers.embed(tokens, params["embed"]).astype(cfg.c_dtype)

    def body(h, pc):
        p, c = pc
        return blocks.tblock_decode(h, p, cfg, c, pos)
    x, cache = jax.lax.scan(body, x, (params["stack"], cache))
    x = layers.apply_norm(x, params["ln_f"], cfg.norm)
    return layers.logits(x, params["unembed"])[:, 0], cache


def _setup(arch, cache_dtype):
    cfg = dataclasses.replace(get_config(arch, tiny=True),
                              cache_dtype_str=cache_dtype)
    model = build_model(cfg)
    params = init_params(model.specs(), jax.random.PRNGKey(3))
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    shape = (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.head_dim)
    cache = {"k": jax.random.normal(ks[0], shape).astype(cfg.cache_dtype),
             "v": jax.random.normal(ks[1], shape).astype(cfg.cache_dtype)}
    tokens = jax.random.randint(ks[2], (B, 1), 0, cfg.vocab)
    return cfg, model, params, cache, tokens


POSITIONS = {
    "rows": [4, 9, 31, 0],
    "row_at_S": [4, S, 17, 2],
    "row_negative": [-1, 9, 31, 5],
    "scalar": 7,
}


@pytest.mark.parametrize("cache_dtype", ["f32", "bf16"])
@pytest.mark.parametrize("where", list(POSITIONS))
@pytest.mark.parametrize("arch", ["qwen3-4b", "granite-moe-1b-a400m"])
def test_carried_cache_write_matches_onehot_loop(arch, where, cache_dtype):
    cfg, model, params, cache, tokens = _setup(arch, cache_dtype)
    assert cfg.family in ("dense", "moe") and cfg.cache_update == "dus"
    pos = jnp.asarray(POSITIONS[where], jnp.int32)
    want_lg, want = jax.jit(_onehot_decode_step, static_argnums=0)(
        cfg, params, cache, tokens, pos)
    got_lg, got = jax.jit(model.decode_step)(params, dict(cache),
                                             {"tokens": tokens}, pos)
    masked = build_model(dataclasses.replace(cfg, cache_update="masked"))
    m_lg, m_cache = jax.jit(masked.decode_step)(params, dict(cache),
                                                {"tokens": tokens}, pos)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))
        np.testing.assert_array_equal(np.asarray(m_cache[name]),
                                      np.asarray(want[name]))
        # only [layer, row, pos[row]] changed; rows outside [0, S) not at all
        old, new = np.asarray(cache[name]), np.asarray(got[name])
        changed = (old != new).any(axis=(3, 4))                # [L, B, S]
        rows = np.broadcast_to(np.asarray(pos), (B,))
        hit = np.zeros((B, S), bool)
        for b, p in enumerate(rows):
            if 0 <= p < S:
                hit[b, p] = True
        assert not (changed & ~hit[None]).any()
        assert changed[:, hit].all()
    np.testing.assert_array_equal(np.asarray(got_lg), np.asarray(want_lg))
    np.testing.assert_array_equal(np.asarray(m_lg), np.asarray(want_lg))


@pytest.mark.parametrize("mode", ["dus", "masked"])
def test_decode_step_hlo_writes_cache_as_the_mode_says(mode):
    """'dus' scatters one position per row into the carried stack; 'masked'
    keeps the one-hot select over the sequence dim, and scatters nothing."""
    cfg = dataclasses.replace(get_config("qwen3-4b", tiny=True),
                              cache_update=mode)
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model.specs(), jax.random.PRNGKey(0)))
    kv = jax.ShapeDtypeStruct((cfg.n_layers, B, S, cfg.n_kv_heads,
                               cfg.head_dim), cfg.cache_dtype)
    hlo = jax.jit(model.decode_step).lower(
        params, {"k": kv, "v": kv},
        {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)},
        jax.ShapeDtypeStruct((B,), jnp.int32)).as_text()
    if mode == "dus":
        assert hlo.count('"stablehlo.scatter"') == 2
    else:
        assert '"stablehlo.scatter"' not in hlo
        slab = f"tensor<{B}x{S}x{cfg.n_kv_heads}x{cfg.head_dim}xi1>"
        assert slab in hlo
