"""MoE routing: dispatch-engine equivalence, capacity semantics (einsum),
the dropless sort engine, sigmoid selection with a correction bias."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sharding import init_params
from repro.models import moe


def _params(d=32, ff=64, E=4, key=jax.random.PRNGKey(0)):
    return init_params(moe.moe_specs(d, ff, E), key)


def test_sort_and_einsum_dispatch_agree_without_drops():
    """With capacity ample enough that einsum drops nothing, it computes
    what the dropless sort engine does; sort counts every assignment."""
    d, ff, E, k = 32, 64, 4, 2
    p = _params(d, ff, E)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d)) * 0.5
    y1, s1 = moe.apply_moe(x, p, top_k=k, group_size=32, cap_factor=8.0,
                           dispatch="einsum")
    y2, s2 = moe.apply_moe(x, p, top_k=k, group_size=32, cap_factor=8.0,
                           dispatch="sort")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-3,
                               atol=2e-4)
    np.testing.assert_allclose(float(s1["aux"]), float(s2["aux"]), rtol=1e-5)
    assert int(s2["moe_assigned"]) == 2 * 16 * k


def test_dense_equivalence_with_full_capacity_topE():
    """top_k == E with ample capacity == dense mixture over all experts."""
    d, ff, E = 16, 32, 4
    p = _params(d, ff, E, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, d)) * 0.5
    y, _ = moe.apply_moe(x, p, top_k=E, group_size=8, cap_factor=E * 2.0,
                         dispatch="einsum")
    # dense reference
    logits = x.reshape(-1, d) @ p["router"]
    w = jax.nn.softmax(logits, -1)
    xin = jnp.broadcast_to(x.reshape(-1, d)[None], (E, 8, d))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xin, p["w_up"])
    yo = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    want = jnp.einsum("te,etd->td", w, yo).reshape(1, 8, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-3,
                               atol=2e-4)


def test_capacity_drops_tokens_not_crash():
    d, ff, E = 16, 32, 4
    p = _params(d, ff, E, jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 64, d))
    # capacity factor tiny -> most tokens dropped, output finite & small
    y, st = moe.apply_moe(x, p, top_k=2, group_size=64, cap_factor=0.1,
                          dispatch="einsum")
    assert bool(jnp.isfinite(y).all())
    assert float(jnp.abs(y).mean()) < float(jnp.abs(x).mean()) * 10
    assert np.isfinite(float(st["aux"]))


def test_capacity_rounding():
    assert moe.capacity(512, 8, 2, 1.25) == 160
    assert moe.capacity(512, 8, 2, 1.25) % 8 == 0
    assert moe.capacity(8, 64, 1, 1.0) >= 8  # floor


def test_router_weights_normalized():
    w = jax.random.normal(jax.random.PRNGKey(6), (16, 8))
    x = jax.random.normal(jax.random.PRNGKey(7), (32, 16))
    gw, gi, aux = moe.router_probs(x, w, 2)
    np.testing.assert_allclose(np.asarray(gw.sum(-1)), 1.0, rtol=1e-5)
    assert int(gi.max()) < 8 and int(gi.min()) >= 0
    # top-k ids are distinct per token
    assert bool((gi[:, 0] != gi[:, 1]).all())


def test_aux_loss_penalizes_imbalance():
    d, E = 8, 4
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (256, d))) + 0.1
    # balanced router: expert e keyed to feature e -> ~uniform assignment
    w_bal = jnp.zeros((d, E))
    for e in range(E):
        w_bal = w_bal.at[e, e].set(10.0)
    _, gi, aux_b = moe.router_probs(x, w_bal, 1)
    counts = jnp.bincount(gi[:, 0], length=E)
    assert int(counts.min()) > 0          # genuinely spread
    # router that always picks expert 0 (positive inputs) -> aux near E
    w_collapse = jnp.zeros((d, E)).at[:, 0].set(10.0)
    _, _, aux_c = moe.router_probs(x, w_collapse, 1)
    assert float(aux_c) > float(aux_b) * 1.5
    assert float(aux_c) > 0.9 * E  # collapsed ~ E


def _dense_expert(x, p, e):
    """Expert ``e``'s SwiGLU on every row of x [T, d]."""
    h = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
    return h @ p["w_down"][e]


def test_sort_dispatch_is_dropless_when_every_token_picks_one_expert():
    """Every token routes to expert 0: the einsum engine's capacity (1.25
    of an even share) drops most of them; the sort engine drops none."""
    d, ff, E, T = 16, 32, 4, 64
    p = _params(d, ff, E, jax.random.PRNGKey(9))
    p["router"] = jnp.zeros((d, E)).at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(10), (1, T, d))) + 0.1
    want = _dense_expert(x[0], p, 0)              # weight 1 after top-1 norm
    y, st = moe.apply_moe(x, p, top_k=1, dispatch="sort")
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert int(st["moe_assigned"]) == T and int(st["moe_max_load"]) == T
    y_cap, _ = moe.apply_moe(x, p, top_k=1, group_size=T, cap_factor=1.25,
                             dispatch="einsum")
    lost = np.all(np.asarray(y_cap[0]) == 0, axis=-1)
    assert lost.sum() >= T // 2                   # capacity dropped them


def test_sigmoid_bias_changes_the_choice_not_the_weights():
    """A correction bias moves which experts are chosen; their weights are
    still the unbiased sigmoid scores, normalised over the chosen and
    scaled."""
    d, E, k, T = 16, 8, 2, 32
    x = jax.random.normal(jax.random.PRNGKey(11), (T, d))
    w = jax.random.normal(jax.random.PRNGKey(12), (d, E)) * 0.3
    p = {"router": w, "router_bias": jnp.zeros((E,))}
    w0, ids0, _ = moe.route(x, p, k, "sigmoid", 2.5)
    bias = jnp.zeros((E,)).at[E - 1].set(10.0)    # expert E-1 always wins
    w1, ids1, _ = moe.route(x, dict(p, router_bias=bias), k, "sigmoid", 2.5)
    assert bool((ids1 == E - 1).any(-1).all())
    assert not bool((ids0 == E - 1).any(-1).all())
    scores = jax.nn.sigmoid(x @ w)
    for ids, wt in ((ids0, w0), (ids1, w1)):
        s = jnp.take_along_axis(scores, ids, -1)
        np.testing.assert_allclose(np.asarray(wt),
                                   np.asarray(s / s.sum(-1, keepdims=True)
                                              * 2.5), rtol=1e-6)
