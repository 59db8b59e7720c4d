"""The plain reference against the program's model at tiny size on the
CPU: the same weights from the seed, bit for bit, and the same logits
through prefill and then decode through the cache.  Also the control: it
reads far worse than the program would."""
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import REPO, tiny_config

sys.path[:0] = [str(REPO / "bench"), str(REPO / "src")]
import reference  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.sharding import init_params  # noqa: E402
from repro.models.model import build_model  # noqa: E402

# the benchmark's Qwen3 configuration, and the same with Qwen2's switches
# (bias on q, k and v; no qk-norm)
CONFIGS = ["qwen3", "qwen2"]


def config(name):
    cfg = tiny_config("qwen3-4b")
    if name == "qwen2":
        cfg.update(qk_norm=False, attention_bias=True)
    return cfg


def program_model(name, dtype):
    cfg = config(name)
    m = reference.dims(cfg)
    arch = dataclasses.replace(
        get_config(cfg["arch"]), n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["Hkv"], head_dim=m["hd"], d_ff=m["ff"],
        vocab=m["V"], rope_theta=m["theta"], qk_norm=m["qk_norm"],
        qkv_bias=m["bias"], param_dtype=dtype, compute_dtype="f32",
        cache_dtype_str="f32", remat=False)
    return cfg, build_model(arch)


def flat(tree, prefix=()):
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(flat(v, prefix + (k,)))
        return out
    return {prefix: tree}


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("dtype", ["bf16", "f32"])
def test_weights_match_the_program_bit_for_bit(name, dtype):
    cfg, model = program_model(name, dtype)
    seed = 2_147_483_659 % 2 ** 31
    prog = flat(init_params(model.specs(), jax.random.PRNGKey(seed)))
    ref = reference.make_weights(
        cfg, seed, jnp.bfloat16 if dtype == "bf16" else jnp.float32)
    assert sorted(prog) == sorted(ref)
    for k in prog:
        assert prog[k].dtype == ref[k].dtype, k
        np.testing.assert_array_equal(np.asarray(prog[k]),
                                      np.asarray(ref[k]), err_msg=str(k))


@pytest.mark.parametrize("name", CONFIGS)
def test_logits_match_prefill_then_decode(name):
    cfg, model = program_model(name, "f32")
    seed, P, G = 11, 12, 6
    params = init_params(model.specs(), jax.random.PRNGKey(seed))
    w = reference.make_weights(cfg, seed, jnp.float32)
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg["vocab_size"], P + G).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        lg, cache = model.prefill(params, {"tokens": jnp.asarray(seq[None, :P])},
                                  P + G)
        prog = [lg[0]]
        for j in range(G - 1):
            lg, cache = model.decode_step(
                params, cache, {"tokens": jnp.asarray(seq[None, P + j:P + j + 1])},
                jnp.asarray([P + j], jnp.int32))
            prog.append(lg[0])
    prog = np.stack([np.asarray(x) for x in prog])
    ref = np.asarray(reference.logits(cfg, w, seq[:P + G - 1], first=P - 1))
    assert ref.shape == prog.shape
    np.testing.assert_allclose(prog, ref, rtol=2e-4, atol=2e-4)


def test_served_gaps_zero_for_the_reference_own_tokens():
    cfg = tiny_config("qwen3-4b")
    w = reference.make_weights(cfg, 3)
    prompt = np.arange(10, dtype=np.int32)
    served = []
    seq = list(prompt)
    for _ in range(5):
        nxt = int(np.argmax(np.asarray(reference.logits(cfg, w, np.array(
            seq, np.int32), first=len(seq) - 1))[0]))
        served.append(nxt)
        seq.append(nxt)
    gaps, ctl = reference.served_gaps(cfg, w, prompt, served, control=True)
    assert gaps.shape == (5,) and np.all(gaps == 0.0)
    assert ctl.shape == (5,) and np.all(ctl >= 0.0)


def test_fp8_control_reads_worse_than_bf16_weights():
    """The serving control: fp8 rounding moves the top token where the
    program's own bf16 weights, computed in float32, do not."""
    cfg = tiny_config("qwen3-4b")
    w = reference.make_weights(cfg, 5)
    seq = np.random.default_rng(1).integers(0, 512, 64).astype(np.int32)
    ref = np.asarray(reference.logits(cfg, w, seq))
    ctl = np.asarray(reference.logits(cfg, w, seq, quant="fp8"))
    top = ctl.argmax(-1)
    gap = ref.max(-1) - ref[np.arange(len(seq)), top]
    assert gap.max() > 0.0
    assert np.abs(ctl - ref).max() > 10 * np.abs(ref).max() * 2.0 ** -24
