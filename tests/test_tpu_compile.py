"""Compile the main path's kernels and decode step for a TPU v5e.

The TPU compiler is installed with jaxlib's TPU plugin and compiles for a
chip that is described, not attached: these tests run nothing, but they
refuse what the chip's compiler would refuse (block tiling, VMEM, memory).
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
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
