"""Loop-aware HLO cost analysis: the roofline's source of truth."""
import jax
import jax.numpy as jnp

from helpers import run_devices
from repro.core import hlo_analysis, hlo_costs


def _costs(fn, *args):
    co = jax.jit(fn).lower(*args).compile()
    return hlo_costs.analyze(co.as_text(), 1)


def test_scan_flops_equal_unrolled():
    def one(x, w):
        return jnp.tanh(x @ w), None

    def scanned(x, ws):
        y, _ = jax.lax.scan(one, x, ws)
        return y

    def unrolled(x, ws):
        for i in range(12):
            x, _ = one(x, ws[i])
        return x

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 256, 256), jnp.float32)
    cs = _costs(scanned, x, ws)
    cu = _costs(unrolled, x, ws)
    want = 12 * 2 * 128 * 256 * 256
    assert cs.flops == want
    assert cu.flops == want
    # byte models legitimately differ across program forms (loop-carried
    # state vs static slices); they must agree within 2x
    assert 0.5 < cs.bytes / cu.bytes < 2.0


def test_nested_scan_multiplies():
    def inner(x, w):
        return x @ w, None

    def outer(x, ws):
        def body(c, _):
            y, _ = jax.lax.scan(inner, c, ws)
            return y, None
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    c = _costs(outer, x, ws)
    assert c.flops == 5 * 3 * 2 * 64 * 64 * 64


def test_dot_flops_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)
    a = jax.ShapeDtypeStruct((4, 32, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32)
    c = _costs(f, a, b)
    assert c.flops == 2 * 4 * 32 * 8 * 16


def test_collectives_inside_scan_are_multiplied():
    out = run_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import hlo_costs
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ('d',))

        def body(x):
            def step(c, _):
                return jax.lax.psum(c, 'd'), None
            y, _ = jax.lax.scan(step, x, None, length=7)
            return y

        from jax import shard_map
        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P(),
                                  out_specs=P(), check_vma=False))
        co = f.lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
        c = hlo_costs.analyze(co.as_text(), 4)
        print('COUNT', c.coll_counts.get('all-reduce', 0))
        print('WIRE', c.total_wire_bytes)
    """, n_devices=4)
    count = float(out.split("COUNT", 1)[1].split()[0])
    wire = float(out.split("WIRE", 1)[1].split()[0])
    assert count == 7
    want = 7 * 2 * (128 * 128 * 4) * 3 / 4   # 7 ring all-reduces
    assert abs(wire - want) / want < 0.01


def test_wire_byte_model_all_gather():
    txt = '''
ENTRY %main (p: f32[64,128]) -> f32[256,128] {
  %p = f32[64,128]{1,0} parameter(0)
  ROOT %ag = f32[256,128]{1,0} all-gather(%p), replica_groups=[1,4]<=[4], dimensions={0}
}
'''
    c = hlo_costs.analyze(txt, 4)
    s = 256 * 128 * 4
    assert abs(c.total_wire_bytes - s * 3 / 4) < 1
    assert c.coll_counts["all-gather"] == 1


def test_shape_bytes_parses_tuples_and_dtypes():
    assert hlo_analysis._shape_bytes("(f32[2,3], bf16[4])") == 24 + 8
    assert hlo_analysis._shape_bytes("pred[8]") == 8
    assert hlo_analysis._shape_bytes("u32[2,2]{1,0}") == 16


def test_dynamic_slice_counts_window_not_buffer():
    def f(stack, i):
        return jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)
    stack = jax.ShapeDtypeStruct((100, 128, 128), jnp.float32)
    c = _costs(f, stack, jax.ShapeDtypeStruct((), jnp.int32))
    # window is 64KB; full buffer is 6.4MB - must count ~window-sized traffic
    assert c.bytes < 1e6, c.bytes


def test_dus_rooted_fusion_counts_update_not_buffer():
    """Regression (xlstm §Perf C2 investigation): a fusion whose root is a
    dynamic-update-slice must count the updated row, not the whole aliased
    buffer."""
    txt = '''
%fused_dus (param_0: f32[100,64], param_1: f32[1,64], param_2: s32[]) -> f32[100,64] {
  %param_0 = f32[100,64]{1,0} parameter(0)
  %param_1 = f32[1,64]{1,0} parameter(1)
  %param_2 = s32[] parameter(2)
  %c = s32[] constant(0)
  ROOT %dus = f32[100,64]{1,0} dynamic-update-slice(%param_0, %param_1, %param_2, %c)
}

ENTRY %main (a: f32[100,64], b: f32[1,64], i: s32[]) -> f32[100,64] {
  %a = f32[100,64]{1,0} parameter(0)
  %b = f32[1,64]{1,0} parameter(1)
  %i = s32[] parameter(2)
  ROOT %f = f32[100,64]{1,0} fusion(%a, %b, %i), kind=kLoop, calls=%fused_dus
}
'''
    c = hlo_costs.analyze(txt, 1)
    # 3x the 256-byte row (update read + window read/write), NOT ~51 KB
    assert c.bytes < 2048, c.bytes


def test_fusion_param_sliced_inside_counts_window():
    """The scan-over-layers pattern: a fusion that only dynamic-slices a
    stacked parameter buffer reads one layer's slice, not the stack."""
    txt = '''
%fused_ds (param_0: f32[48,1024], param_1: s32[]) -> f32[1,1024] {
  %param_0 = f32[48,1024]{1,0} parameter(0)
  %param_1 = s32[] parameter(1)
  %c = s32[] constant(0)
  ROOT %ds = f32[1,1024]{1,0} dynamic-slice(%param_0, %param_1, %c), dynamic_slice_sizes={1,1024}
}

ENTRY %main (a: f32[48,1024], i: s32[]) -> f32[1,1024] {
  %a = f32[48,1024]{1,0} parameter(0)
  %i = s32[] parameter(1)
  ROOT %f = f32[1,1024]{1,0} fusion(%a, %i), kind=kLoop, calls=%fused_ds
}
'''
    c = hlo_costs.analyze(txt, 1)
    assert c.bytes < 3 * 4096 + 64, c.bytes  # window-sized, not 192 KB
