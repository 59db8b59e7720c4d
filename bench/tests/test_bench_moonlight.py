"""The Moonlight cell's harness: the reference's sizes and flops at the
cell's widths, the runner's roofline, and at a small size on the CPU the
cell run end to end, with the faults of ``bench/moe_faults.py`` planted."""
import json
import os
import subprocess
import sys
import types

import pytest

from bench_helpers import REPO, make_root

sys.path[:0] = [str(REPO / "bench"), str(REPO / "src")]
import moonlight  # noqa: E402
import scopes  # noqa: E402
import train_moe  # noqa: E402

CELL = "moonlight-16b-a3b.train-8k"
CONFIG = json.loads((REPO / "bench" / "configs" /
                     "moonlight-16b-a3b.json").read_text())
TRAFFIC = json.loads((REPO / "bench" / "traffic" / "train-8k.json")
                     .read_text())
# d 64, 4 heads, small latent ranks; 4 of 8 routed experts held, 3 a token
TINY = dict(CONFIG, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
            moe_intermediate_size=32, n_routed_experts=4,
            num_experts_per_tok=3, num_hidden_layers=3, vocab_size=256,
            published=dict(CONFIG["published"], n_routed_experts=8))
TINY_TRAFFIC = dict(TRAFFIC, batch=2, seq=64)
TINY_LIMITS = {"grad_leaf_gap": 0.003, "change_leaf_gap": 0.1,
               "grad_norm_rel_gap": 0.0015, "nonfinite_steps": 0}


def test_cell_sizes_match_the_deployment():
    """669M parameters held (the dense layer, 5 MoE layers with 8 of 64
    experts, a 20,480-row slice of the vocabulary): 10.7 GB at 16 bytes a
    parameter; a step of 2 x 8192 tokens at the expected load (6 of 64
    experts a token, 8 held) is about 4.3e13 flops."""
    m = moonlight.dims(CONFIG)
    assert (m["E"], m["E_held"], m["k"]) == (64, 8, 6)
    assert abs(moonlight.n_params(m) - 669e6) < 2e6
    T = 2 * 8192
    assigned = T * 6 * 8 // 64 * 5
    f = moonlight.flops(m, 2, 8192, assigned)
    assert abs(f - 4.3e13) / 4.3e13 < 0.02
    w = moonlight.matmul_weights(m)
    per_token = (6 * w["attn"] + w["dense"] + 5 * w["moe"] + w["head"]
                 + assigned * w["expert"] / T)
    assert abs(per_token - 313e6) < 1e6


def test_weight_rule_is_the_program_tree():
    """The reference draws every leaf of the program's parameters, by the
    program's paths and shapes, and nothing else."""
    import dataclasses
    from repro.configs import get_config
    from repro.core.sharding import ParamSpec
    from repro.models.model import build_model
    cfg = dataclasses.replace(get_config(CONFIG["arch"]),
                              **moonlight.plan_overrides(CONFIG))
    specs = build_model(cfg).specs()
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))[0]
    prog = {tuple(k.key for k in p): s.shape for p, s in leaves}
    ref = {p: s for p, s, _, _ in moonlight.weight_rule(CONFIG)}
    assert prog == ref
    assert sum(int(__import__("math").prod(s)) for s in ref.values()) == \
        moonlight.n_params(moonlight.dims(CONFIG))


def test_roofline_counts_the_held_experts_from_moe_assigned():
    cell = types.SimpleNamespace(traffic=TRAFFIC, dims=moonlight.dims(CONFIG),
                                 reference=moonlight, chips=1)
    peak = {"bf16_flops_per_s": 197e12}
    rec = {"steps": 10, "moe_assigned": 0}
    base = train_moe.roofline_s(cell, rec, peak)
    assert rec["experts_roofline_s"] == 0
    rec["moe_assigned"] = 614400
    more = train_moe.roofline_s(cell, rec, peak)
    extra = moonlight.expert_flops(cell.dims, 614400) / 197e12
    assert abs(more - base - extra) < 1e-9 * more
    assert abs(rec["experts_roofline_s"] - extra) < 1e-12


def test_op_scopes_names_the_moe_and_mla_ops():
    """The compiled step's ops carry their named scope in the HLO metadata,
    forward and backward."""
    import jax
    import jax.numpy as jnp
    from repro.frontend import Plan
    plan = Plan(arch=CONFIG["arch"], tiny=True, batch=2, seq=16)
    with plan.compile() as session:
        step = session.train_step
        params, opt = step.init(jax.random.PRNGKey(0))
        batch = {k: jnp.zeros((2, 16), jnp.int32)
                 for k in ("tokens", "labels")}
        text = step.fn.lower(params, opt, batch).compile().as_text()
    found = set(scopes.op_scopes(text).values())
    assert {"mla", "moe.route", "moe.dispatch", "moe.experts",
            "moe.combine", "moe.shared"} <= found


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("moe"), [
        (CELL, TINY, TINY_TRAFFIC, 1)], limits={CELL: TINY_LIMITS})
    bench = json.loads((root / "BENCHMARK.json").read_text())
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    for key in ("end_to_end", "per_layer"):
        have = {e["name"] for e in bench[key]}
        for e in real[key]:
            if CELL in e.get("workloads", []) and e["name"] not in have:
                bench[key].append(dict(e, workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def run_moe_cell(root, *, fault="", trace=0, control=0, seed=5):
    """``bench_helpers.run_cell`` with a fault of ``bench/moe_faults.py``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"))
    env.pop("PYTHONPATH", None)
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, str(REPO / "bench" / "tests" / "moe_drive.py"),
           str(root), fault, "--workload", CELL, "--seed", str(seed),
           "--seconds", "1", "--trace", str(trace), "--control",
           str(control)]
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=600)
    lines = p.stdout.strip().splitlines()
    return p.returncode, json.loads(lines[-1]) if lines else None, p.stderr


@pytest.fixture(scope="module")
def clean_run(moe_root):
    return run_moe_cell(moe_root)


def test_moe_cell_runs_and_is_correct(clean_run):
    rc, res, err = clean_run
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert set(res["metrics"]) == {"train_tok_s", "setup_s"}
    window = next(x for x in err.splitlines() if x.startswith("window:"))
    assert "compiles in window 0" in window and "moe_assigned" in window


@pytest.mark.parametrize("fault", ["routed_scale", "shared", "capacity"])
def test_planted_faults_move_the_compared_numbers(moe_root, clean_run,
                                                  fault):
    """Step 1's gradient shows each fault (on this seed the program reads
    0.0015, the faults 0.044 to 1.0); Adam's normalised change barely
    shows the scale and the capacity (2.8 to 3.7 times the program's)."""
    _, clean, _ = clean_run
    rc, res, err = run_moe_cell(moe_root, fault=fault)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, err[-2000:]
    gap = res["checks"]["grad_leaf_gap"]["value"]
    assert gap > 10 * clean["checks"]["grad_leaf_gap"]["value"]
    assert gap > TINY_LIMITS["grad_leaf_gap"]
