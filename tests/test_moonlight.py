"""Moonlight-16B-A3B's training path (latent attention, a leading dense
layer, sigmoid-routed dropless experts held as a share, shared experts)
against the plain float32 reference ``bench/moonlight.py``, at a small size
on the CPU in float32."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench"))
import moonlight  # noqa: E402

from repro.core.steps import Strategy  # noqa: E402
from repro.frontend import Plan  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.optim.optimizers import OptConfig  # noqa: E402

B, S = 2, 32
# d 64, 4 heads, small latent ranks; 8 routed experts of which a share of 4
# is held (experts 4-7); 1 dense layer, then 2 MoE layers
CFG = {"arch": "moonlight-16b-a3b", "hidden_size": 64,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "kv_lora_rank": 32, "intermediate_size": 128,
       "moe_intermediate_size": 32, "n_routed_experts": 4,
       "expert_offset": 4, "published": {"n_routed_experts": 8},
       "num_experts_per_tok": 3, "n_shared_experts": 2,
       "routed_scaling_factor": 2.446, "first_k_dense_replace": 1,
       "num_hidden_layers": 3, "vocab_size": 256, "rope_theta": 50000.0,
       "rms_norm_eps": 1e-5, "initializer_range": 0.2}


def _batch(seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, CFG["vocab_size"], (B, S + 1)).astype(np.int32)
    return {"tokens": x[:, :-1], "labels": x[:, 1:]}


class _Stream:
    def batch_at(self, step):
        return _batch(step)


def _first_step(seed=3):
    """``Session.train``'s first step from the reference's weights: the
    loss, and the gradient as the optimizer got it (Adam's first moment
    over 1 - b1; no clipping)."""
    b1 = 0.9
    opt = OptConfig(kind="adamw", lr=1e-3, b1=b1, b2=0.95, eps=1e-8,
                    weight_decay=0.0, grad_clip=1e9)
    plan = Plan(arch=CFG["arch"], tiny=False, batch=B, seq=S, seed=seed,
                strategy=Strategy(opt=opt),
                overrides={**moonlight.plan_overrides(CFG),
                           "compute_dtype": "f32", "q_chunk": 8,
                           "kv_chunk": 8})
    got = {}
    with plan.compile() as session:
        step = session.train_step
        real_init, real_fn = step.init, step.fn

        def init(key):
            params, opt_state = real_init(key)
            w = moonlight.make_weights(CFG, seed)
            treedef = jax.tree.structure(params)
            paths = [tuple(k.key for k in p) for p, _ in
                     jax.tree_util.tree_flatten_with_path(params)[0]]
            return jax.tree.unflatten(treedef, [w[p] for p in paths]), \
                opt_state

        def fn(params, opt_state, batch):
            metrics, params, opt_state = real_fn(params, opt_state, batch)
            got["metrics"] = jax.device_get(metrics)
            got["grad"] = {tuple(k.key for k in p): np.asarray(v) / (1 - b1)
                           for p, v in jax.tree_util.tree_flatten_with_path(
                               opt_state["m"])[0]}
            return metrics, params, opt_state
        step.init, step.fn = init, fn
        session.train(_Stream(), steps=1, ckpt_dir="", verbose=False)
    return got


@pytest.fixture(scope="module")
def first_step():
    got = _first_step()
    w = moonlight.make_weights(CFG, 3)
    loss, grad = moonlight.loss_and_grad(CFG, w, _batch(0), rows=B)
    return got, float(loss), {k: np.asarray(v) for k, v in grad.items()}


def test_first_step_loss_matches_reference(first_step):
    got, loss, _ = first_step
    # both float32; the program sums in another order (online softmax over
    # key blocks, a grouped matmul over sorted rows): a few ulps of a loss
    # near log(256)
    np.testing.assert_allclose(float(got["metrics"]["loss"]), loss,
                               rtol=2e-6)


def test_first_step_gradient_matches_reference_leaf_by_leaf(first_step):
    got, _, grad = first_step
    assert set(got["grad"]) == set(grad)
    norms = {k: np.linalg.norm(v) for k, v in grad.items()}
    med = float(np.median(list(norms.values())))
    for k, want in grad.items():
        # float32 on both sides: each leaf within 1e-4 of its own norm (or
        # of the median leaf's, for leaves whose gradient is near zero, as
        # the correction bias's, which only selects, is exactly)
        err = np.linalg.norm(got["grad"][k] - want)
        assert err <= 1e-4 * max(norms[k], med), (k, err, norms[k])
    assert float(np.abs(got["grad"][("stack", "moe", "router_bias")]).max()) \
        == 0.0


def test_first_step_counts_held_assignments(first_step):
    got, _, _ = first_step
    m = got["metrics"]
    n_moe = CFG["num_hidden_layers"] - CFG["first_k_dense_replace"]
    assert 0 < int(m["moe_assigned"]) <= n_moe * B * S * 3
    assert 0 < int(m["moe_max_load"]) <= B * S


def _share(w, s, n, shared):
    """Share ``s`` of ``n`` of MoE layer 0's experts, as ``moe.apply_moe``
    takes it."""
    p = {k[2:]: v[0] for k, v in w.items() if k[:2] == ("stack", "moe")}
    per = p[("w_gate",)].shape[0] // n
    out = {"router": p[("router",)], "router_bias": p[("router_bias",)]}
    for name in ("w_gate", "w_up", "w_down"):
        out[name] = p[(name,)][s * per:(s + 1) * per]
    if shared:
        out["shared"] = {n: p[("shared", n)] for n in
                         ("w_gate", "w_up", "w_down")}
    return out


def test_shares_add_up_to_the_whole_layer():
    """Each share of the 8 experts routes over all 8 and computes its own
    experts' part; the parts of both shares, with the shared experts
    counted once, are the uncut layer.  Counting the shared experts once
    per share is not."""
    full = dict(CFG, n_routed_experts=8, expert_offset=0)
    w = moonlight.make_weights(full, 11)
    w[("stack", "moe", "router_bias")] = jax.random.normal(
        jax.random.PRNGKey(1), w[("stack", "moe", "router_bias")].shape) * 0.1
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 48, 64))
    want = np.asarray(moonlight.layer_out(full, w, h[0]))
    kw = dict(top_k=3, dispatch="sort", scoring="sigmoid",
              routed_scale=2.446)
    with jax.default_matmul_precision("highest"):
        parts = [moe.apply_moe(h, _share(w, s, 2, shared=s == 0),
                               expert_offset=4 * s, **kw)[0]
                 for s in range(2)]
        every = [moe.apply_moe(h, _share(w, s, 2, shared=True),
                               expert_offset=4 * s, **kw)[0]
                 for s in range(2)]
    got = np.asarray(sum(parts)[0])
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)
    twice = np.asarray(sum(every)[0])
    assert np.abs(twice - want).max() > 100 * 1e-5 * scale


def test_serving_refuses_latent_attention():
    plan = Plan(arch=CFG["arch"], tiny=True, batch=2, seq=16)
    with plan.compile() as session:
        with pytest.raises(ValueError, match="latent attention"):
            session.serve_stream(requests=1, prompt_len=8, gen_len=2,
                                 slots=1, verbose=False)
        with pytest.raises(ValueError, match="latent attention"):
            session.serve(requests=1, prompt_len=8, gen_len=2, slots=1,
                          verbose=False)
