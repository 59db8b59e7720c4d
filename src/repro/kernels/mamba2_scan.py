"""Mamba-2 SSD chunked scan - Pallas TPU kernel.

Grid: (B, H, num_chunks) with the chunk dimension innermost; TPU grids
execute sequentially, so the running SSM state [P, N] lives in VMEM scratch
and carries across chunk steps (reset at chunk 0).  Each step computes, for
one (batch, head, chunk):

  intra:  y_d = (C B^T (.) exp(segsum(a))) xdt          [c, P]
  carry:  S  <- exp(sum a) * S + sum_s exp(a_cs[-1]-a_cs[s]) B_s (x) xdt_s
  inter:  y_o = C S_prev (.) exp(a_cs)

which is the same block structure as models/ssm.mamba2_chunked, but with
the chunk working set ((3c*N + 2cP + c*c + P*N) * 4B) held in VMEM and the
inter-chunk recurrence carried on-chip instead of through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(xdt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref, s_scr, *,
            chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    xdt = xdt_ref[0, 0].astype(jnp.float32)        # [c, P]
    a = a_ref[0, 0].astype(jnp.float32)            # [1, c] row
    Bm = b_ref[0, 0].astype(jnp.float32)           # [c, N]
    Cm = c_ref[0, 0].astype(jnp.float32)           # [c, N]

    # running sums a_cs[t] = sum_{s<=t} a[s], as a column and as a row:
    # two exact-f32 matmuls with the lower triangle (Mosaic has no cumsum)
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri_f = tri.astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    a_cs = jax.lax.dot_general(tri_f, a, nt, precision=hi,
                               preferred_element_type=jnp.float32)  # [c, 1]
    a_cs_row = jax.lax.dot_general(a, tri_f, nt, precision=hi,
                                   preferred_element_type=jnp.float32)
    # intra-chunk: L[t,s] = exp(a_cs[t]-a_cs[s]) for t>=s
    diff = a_cs - a_cs_row                         # [c, c]
    Lmat = jnp.where(tri, jnp.exp(diff), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [c, c]
    y = jax.lax.dot_general(CB * Lmat, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [c, P]

    # inter-chunk: contribution of carried state
    decay_from_start = jnp.exp(a_cs)                               # [c, 1]
    y = y + jax.lax.dot_general(Cm * decay_from_start, s_scr[...].T,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    # state update for the next chunk
    a_tot = jnp.sum(a, axis=1, keepdims=True)                      # [1, 1]
    decay_to_end = jnp.exp(a_tot - a_cs)                           # [c, 1]
    s_new = jax.lax.dot_general(xdt, Bm * decay_to_end,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [P,N]
    s_scr[...] = s_scr[...] * jnp.exp(a_tot) + s_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit_state():
        state_out_ref[0, 0] = s_scr[...].astype(state_out_ref.dtype)


def mamba2_chunk_scan(xdt, a, Bm, Cm, *, chunk: int = 128,
                      interpret: bool = False):
    """xdt: [B, H, L, P]; a: [B, H, L]; Bm, Cm: [B, H, L, N].
    Returns (y [B, H, L, P], final state [B, H, P, N])."""
    B, H, L, P = xdt.shape
    N = Bm.shape[-1]
    chunk = min(chunk, L)
    assert L % chunk == 0
    nc = L // chunk

    grid = (B, H, nc)
    kern = functools.partial(_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, ic: (b, h, 0, ic)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ic: (b, h, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), xdt.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, a[:, :, None, :], Bm, Cm)   # [B, H, 1, L]: a (1, chunk) tile
    return y, state
