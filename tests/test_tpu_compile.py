"""Compile the main path's kernels and decode step for a TPU v5e.

The TPU compiler is installed with jaxlib's TPU plugin and compiles for a
chip that is described, not attached: these tests run nothing, but they
refuse what the chip's compiler would refuse (block tiling, VMEM, memory).
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.core import steps as steps_lib
from repro.core.sharding import param_structs
from repro.distrib.collectives import _block_rows
from repro.kernels import flash_attention as fa
from repro.kernels import mamba2_scan as m2
from repro.kernels import onebit as ob
from repro.optim import compression

HBM_BYTES = 16e9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_qwen3_4b_widths(one_chip):
    cfg = get_config("qwen3-4b")
    S = 4096
    q = _struct((1, cfg.n_heads, S, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _struct((1, cfg.n_kv_heads, S, cfg.head_dim), jnp.bfloat16,
                 one_chip)
    _kernel_compiles(lambda q, k, v: fa.flash_attention(q, k, v), q, kv, kv)


def test_mamba2_chunk_scan_compiles_at_zamba2_widths(one_chip):
    cfg = get_config("zamba2-2.7b")
    H = cfg.expand * cfg.d_model // cfg.ssm_head_dim
    L, P, N = 4096, cfg.ssm_head_dim, cfg.ssm_state
    x = _struct((1, H, L, P), jnp.bfloat16, one_chip)
    a = _struct((1, H, L), jnp.float32, one_chip)
    bc = _struct((1, H, L, N), jnp.bfloat16, one_chip)
    _kernel_compiles(
        lambda x, a, b, c: m2.mamba2_chunk_scan(x, a, b, c,
                                                chunk=cfg.ssm_chunk),
        x, a, bc, bc)


def _codec_bucket_rows() -> int:
    """Rows of the largest [R, ROW] bucket the 1-bit codec ships for
    qwen3-4b's gradients."""
    from repro.models.model import build_model
    specs = build_model(get_config("qwen3-4b")).specs()
    f32 = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                       param_structs(specs))
    plan = compression.make_plan(f32, 1)
    return max(b.size for b in plan.buckets) // compression.ROW


def test_onebit_kernels_compile_at_codec_bucket_rows(one_chip):
    R, C = _codec_bucket_rows(), compression.ROW
    bm = _block_rows(R)
    g = _struct((R, C), jnp.float32, one_chip)
    _kernel_compiles(lambda g, e: ob.onebit_quantize(g, e, block_rows=bm),
                     g, g)
    packed = _struct((R, C // 32), jnp.uint32, one_chip)
    scale = _struct((R, 128), jnp.float32, one_chip)
    _kernel_compiles(lambda p, s: ob.onebit_dequantize(p, s, block_rows=bm),
                     packed, scale)


def test_qwen3_4b_decode_step_compiles_and_fits_one_chip(topo):
    cfg = get_config("qwen3-4b")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    slots, cache_len = 4, 160
    shape = {"seq_len": cache_len, "global_batch": slots, "kind": "decode"}
    step = steps_lib.make_decode_step(cfg, mesh, steps_lib.Strategy(), shape)
    compiled = step.fn.lower(
        param_structs(step.specs), param_structs(step.cache_specs),
        steps_lib.input_specs(steps_lib._serve_cfg(cfg), shape),
        jax.ShapeDtypeStruct((slots,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    assert cfg.n_layers == 36
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def _chat_decode_step(topo, mode):
    """The qwen3-4b decode step at the chat cell's 20 slots x 640, compiled
    with cache write ``mode``; returns (compiled, the K cache's struct)."""
    cfg = dataclasses.replace(get_config("qwen3-4b"), cache_update=mode)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    slots, cache_len = 20, 640
    shape = {"seq_len": cache_len, "global_batch": slots, "kind": "decode"}
    step = steps_lib.make_decode_step(cfg, mesh, steps_lib.Strategy(), shape)
    cache = param_structs(step.cache_specs)
    assert cache["k"].shape == (36, slots, cache_len, 8, 128)
    compiled = step.fn.lower(
        param_structs(step.specs), cache,
        steps_lib.input_specs(steps_lib._serve_cfg(cfg), shape),
        jax.ShapeDtypeStruct((slots,), jnp.int32)).compile()
    return compiled, cache["k"]


def _ops(text, op, shape):
    """HLO lines whose result of ``shape`` comes from ``op``."""
    out = "bf16[%s]" % ",".join(map(str, shape))
    pat = r"= %s(\{[^}]*\})? %s\(" % (re.escape(out), op)
    return [ln for ln in text.splitlines() if re.search(pat, ln)]


def test_qwen3_4b_decode_step_writes_the_cache_in_place(topo):
    """At the chat cell's 20 slots x 640, the decode step writes the stacked
    KV cache where it lies: no temporary the size of the cache, and no copy
    of the whole stack (an xs/ys layer scan makes both)."""
    compiled, k = _chat_decode_step(topo, "dus")
    cache_bytes = 2 * k.size * k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 8
    text = compiled.as_text()
    assert not _ops(text, "copy", k.shape)
    assert _ops(text, "scatter", k.shape)


def test_qwen3_4b_masked_decode_step_keeps_the_one_hot_write(topo):
    """mode="masked" (for a cache whose sequence dim is sharded) selects
    each layer's whole slice against a one-hot mask, and scatters nothing."""
    compiled, k = _chat_decode_step(topo, "masked")
    text = compiled.as_text()
    layer = k.shape[1:]
    assert _ops(text, "select", layer) or _ops(text, "select", (1,) + layer)
    assert not _ops(text, "scatter", k.shape)


def test_moonlight_expert_layer_compiles_at_cell_widths(one_chip):
    """The dropless MoE layer of the moonlight-16b-a3b.train-8k cell, its
    forward and backward at 2 x 8192 tokens: 8 held of 64 routed experts,
    6 a token, and the shared experts; the held experts' products are
    grouped matmuls."""
    from repro.models import moe
    cfg = get_config("moonlight-16b-a3b")
    d, held, T = cfg.d_model, 8, 2 * 8192
    specs = moe.moe_specs(d, cfg.moe_d_ff, cfg.n_experts, n_held=held,
                          shared_ff=cfg.n_shared_experts * cfg.moe_d_ff,
                          router_bias=True)
    p = jax.tree.map(lambda s: _struct(s.shape, jnp.float32, one_chip),
                     param_structs(specs))
    x = _struct((2, T // 2, d), jnp.bfloat16, one_chip)

    def loss(p, x):
        y, st = moe.apply_moe(x, p, top_k=cfg.top_k, dispatch="sort",
                              scoring="sigmoid",
                              routed_scale=cfg.routed_scale)
        return jnp.sum(y.astype(jnp.float32)), st["moe_assigned"]

    compiled = jax.jit(jax.value_and_grad(loss, has_aux=True)).lower(
        p, x).compile()
    assert "ragged-dot" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 0.5 * HBM_BYTES


def _mla_layer_grad(one_chip, monkeypatch, kernel):
    """One MLA layer of the moonlight-16b-a3b.train-8k cell, its forward
    and backward at 2 x 8192 tokens (16 heads, q.k 128 + 64, v 128),
    compiled on the kernel path or on the chunked path."""
    from repro.kernels import ops
    from repro.models import attention
    monkeypatch.setattr(ops, "_on_tpu", lambda: kernel)
    cfg = get_config("moonlight-16b-a3b")
    specs = attention.mla_specs(cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                                cfg.qk_nope_dim, cfg.qk_rope_dim,
                                cfg.v_head_dim)
    p = jax.tree.map(lambda s: _struct(s.shape, jnp.float32, one_chip),
                     param_structs(specs))
    x = _struct((2, 8192, cfg.d_model), jnp.bfloat16, one_chip)

    def loss(p, x):
        return jnp.sum(attention.mla_layer(x, p, cfg).astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile()


def test_moonlight_mla_layer_runs_the_fused_kernel(one_chip, monkeypatch):
    """On the kernel path the compiled layer holds the Pallas kernel's
    forward and backward (dq and dkv fused) custom calls, each under the ``mla`` scope that
    bench/scopes.py reads, and plans fewer temporary bytes than the
    chunked path (no float32 logits block reaches HBM)."""
    compiled = _mla_layer_grad(one_chip, monkeypatch, kernel=True)
    text = compiled.as_text()
    # a Pallas call's text spans lines (its kernel metadata): read each
    # call's op_name from its own instruction onwards
    calls = dict(re.findall(r"%(splash_mha_\w+?)(?:\.\d+)? = .*?"
                            r"op_name=\"([^\"]*)\"", text, re.S))
    assert {"splash_mha_fwd_residuals",
            "splash_mha_dkv_no_residuals"} <= set(calls)
    for op_name in calls.values():
        assert re.search(r"(?:^|[/(])mla(?=[/)]|$)", op_name), op_name
    chunked = _mla_layer_grad(one_chip, monkeypatch, kernel=False)
    assert "tpu_custom_call" not in chunked.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < chunked.memory_analysis().temp_size_in_bytes
