"""DDP over the active-message fabric (DESIGN.md §11).

The battery the tentpole is proven by:
  * codec units - fp32 bitwise round-trip, onebit vs the jnp reference,
    exact wire-format byte counts, error-feedback statefulness;
  * ring units - world-1 identity, a real 2-endpoint in-process ring
    (bitwise-identical sums on both ranks, exact wire accounting),
    abort/peer-loss/timeout semantics;
  * plan validation - the ``Plan(ddp=True)`` error surface;
  * multiproc drills (marked) - 2-locality fp32 runs BIT-IDENTICAL in
    loss to a 1-process run over the same shards, onebit converges
    within tolerance over 50 steps, ``grad_wire_bytes`` is asserted
    EXACTLY, and a locality killed mid-all-reduce aborts the run with
    ``LocalityLostError`` instead of hanging.
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import steps as steps_lib
from repro.core.steps import Strategy
from repro.distrib import (Endpoint, Fp32Codec, LocalityLostError,
                           OneBitCodec, RingAllReduce, get_codec)
from repro.frontend.ddp import shard_batch
from repro.frontend.plan import Plan
from repro.optim import compression

ARCH = "qwen2.5-3b"


def _plan(**kw):
    kw.setdefault("arch", ARCH)
    kw.setdefault("batch", 4)
    kw.setdefault("seq", 16)
    kw.setdefault("ddp", True)
    return Plan(**kw)


def _toy_plan(n=4096):
    """A small single-bucket FusionPlan (padded to ROW*32 = 32768)."""
    return compression.make_plan(
        [jax.ShapeDtypeStruct((n,), jnp.float32)], 1)


# -- codecs -------------------------------------------------------------------

def test_get_codec_unknown_raises():
    with pytest.raises(ValueError, match="unknown grad codec"):
        get_codec("fp16")


def test_fp32_codec_roundtrip_is_bitwise():
    plan = _toy_plan()
    rng = np.random.default_rng(0)
    bufs = [rng.standard_normal(b.size).astype(np.float32)
            for b in plan.buckets]
    codec = get_codec("fp32")
    codec.reset(plan)
    payloads = codec.encode(bufs)
    assert [len(p) for p in payloads] == [4 * b.size for b in plan.buckets]
    assert codec.wire_bytes(plan) == sum(4 * b.size for b in plan.buckets)
    for data, buf, b in zip(payloads, bufs, plan.buckets):
        np.testing.assert_array_equal(codec.decode(data, b), buf)


def test_onebit_codec_matches_jnp_reference_and_wire_format():
    plan = _toy_plan()
    b = plan.buckets[0]
    rng = np.random.default_rng(1)
    g = rng.standard_normal(b.size).astype(np.float32)
    codec = get_codec("onebit")
    codec.reset(plan)
    (payload,) = codec.encode([g])
    # wire format: size/8 bytes of sign words + one f32 scale per ROW
    rows = b.size // compression.ROW
    assert len(payload) == b.size // 8 + 4 * rows
    assert codec.wire_bytes(plan) == b.size // 8 + 4 * rows
    # decode == the jnp reference quantizer with zero error state
    packed, scale, _ = compression.quantize_bucket(
        jnp.asarray(g), jnp.zeros((rows, compression.ROW), jnp.float32))
    ref = np.asarray(compression.dequantize_bucket(packed, scale, b.size))
    np.testing.assert_array_equal(codec.decode(payload, b), ref)


def test_onebit_codec_error_feedback_is_stateful():
    """A second encode of the SAME gradient must differ: the residual of
    the first quantization is folded in (and a reset clears it)."""
    plan = _toy_plan()
    g = np.random.default_rng(2).standard_normal(
        plan.buckets[0].size).astype(np.float32)
    codec = get_codec("onebit")
    codec.reset(plan)
    first = codec.encode([g])[0]
    second = codec.encode([g])[0]
    assert first != second
    codec.reset(plan)
    assert codec.encode([g])[0] == first


# -- ring all-reduce ----------------------------------------------------------

def test_ring_world1_is_identity():
    plan = _toy_plan()
    ring = RingAllReduce(None, 1)
    ring.configure("fp32", plan)
    bufs = [np.arange(b.size, dtype=np.float32) for b in plan.buckets]
    summed, metas = ring.allreduce(0, bufs, meta={"loss": 1.5})
    for out, buf in zip(summed, bufs):
        np.testing.assert_array_equal(out, buf)
    assert metas == {0: {"loss": 1.5}}
    assert ring.wire_bytes == 0
    ring.deactivate()


def test_ring_requires_configure():
    with pytest.raises(RuntimeError, match="configure"):
        RingAllReduce(None, 1).allreduce(0, [])


def _two_rings(account=None):
    a, b = Endpoint(0), Endpoint(1)
    a.address_book[1] = b.address
    b.address_book[0] = a.address
    return a, b, RingAllReduce(a, 2, account=account), RingAllReduce(b, 2)


def test_ring_two_endpoints_bitwise_and_exact_accounting():
    """A real 2-rank ring over in-process endpoints: both ranks compute
    the SAME bitwise sum (origin-rank combine order), metas travel with
    bucket 0, and each rank's wire_bytes is exactly one codec encode."""
    counted = []
    a, b, ra, rb = _two_rings(account=counted.append)
    try:
        plan = _toy_plan()
        ra.configure("fp32", plan, gen=7)
        rb.configure("fp32", plan, gen=7)
        rng = np.random.default_rng(3)
        bufs = {r: [rng.standard_normal(bk.size).astype(np.float32)
                    for bk in plan.buckets] for r in (0, 1)}
        out = {}

        def run(ring):
            out[ring.rank] = ring.allreduce(
                5, bufs[ring.rank], meta={"rank": ring.rank}, timeout=30)

        t = threading.Thread(target=run, args=(rb,))
        t.start()
        run(ra)
        t.join(timeout=30)
        assert not t.is_alive()
        for i, bk in enumerate(plan.buckets):
            expect = bufs[0][i].copy() + bufs[1][i]   # rank order 0, 1
            np.testing.assert_array_equal(out[0][0][i], expect)
            np.testing.assert_array_equal(out[1][0][i], expect)
        assert out[0][1] == {0: {"rank": 0}, 1: {"rank": 1}}
        assert out[1][1] == out[0][1]
        per = Fp32Codec().wire_bytes(plan)
        assert ra.wire_bytes == per          # own encode, no relays at W=2
        assert rb.wire_bytes == per
        assert sum(counted) == per           # the account callback saw it
    finally:
        ra.deactivate(), rb.deactivate()
        a.close(), b.close()


def test_ring_onebit_sums_identically_on_both_ranks():
    a, b, ra, rb = _two_rings()
    try:
        plan = _toy_plan()
        ra.configure("onebit", plan, gen=1)
        rb.configure("onebit", plan, gen=1)
        rng = np.random.default_rng(4)
        bufs = {r: [rng.standard_normal(bk.size).astype(np.float32)
                    for bk in plan.buckets] for r in (0, 1)}
        out = {}

        def run(ring):
            out[ring.rank] = ring.allreduce(0, bufs[ring.rank], timeout=30)

        t = threading.Thread(target=run, args=(rb,))
        t.start()
        run(ra)
        t.join(timeout=30)
        for i in range(len(plan.buckets)):
            np.testing.assert_array_equal(out[0][0][i], out[1][0][i])
        per = OneBitCodec().wire_bytes(plan)
        assert ra.wire_bytes == per and rb.wire_bytes == per
        assert 16 * per <= Fp32Codec().wire_bytes(plan)
    finally:
        ra.deactivate(), rb.deactivate()
        a.close(), b.close()


def test_ring_abort_and_peer_lost_raise_locality_lost():
    a, b, ra, rb = _two_rings()
    try:
        plan = _toy_plan()
        bufs = [np.zeros(bk.size, np.float32) for bk in plan.buckets]
        ra.configure("fp32", plan, gen=1)
        ra.abort("drill")
        with pytest.raises(LocalityLostError, match="drill"):
            ra.allreduce(0, bufs, timeout=5)
        # peer_lost poisons ONLY an active ring
        ra.deactivate()
        ra.peer_lost(1)
        ra.configure("fp32", plan, gen=2)    # clears the poison
        ra.peer_lost(1)
        with pytest.raises(LocalityLostError, match="locality 1 died"):
            ra.allreduce(0, bufs, timeout=5)
    finally:
        ra.deactivate(), rb.deactivate()
        a.close(), b.close()


def test_ring_times_out_on_silent_peer():
    a, b, ra, rb = _two_rings()
    try:
        plan = _toy_plan()
        ra.configure("fp32", plan, gen=1)
        rb.configure("fp32", plan, gen=1)    # registered but never sends
        bufs = [np.zeros(bk.size, np.float32) for bk in plan.buckets]
        with pytest.raises(TimeoutError, match="segment"):
            ra.allreduce(0, bufs, timeout=0.4)
    finally:
        ra.deactivate(), rb.deactivate()
        a.close(), b.close()


# -- batch sharding & plan validation -----------------------------------------

def test_shard_batch_contiguous_rows_and_validation():
    batch = {"x": np.arange(24).reshape(6, 4), "y": np.arange(6)}
    parts = [shard_batch(batch, s, 3) for s in range(3)]
    np.testing.assert_array_equal(
        np.concatenate([p["x"] for p in parts]), batch["x"])
    np.testing.assert_array_equal(parts[1]["y"], batch["y"][2:4])
    with pytest.raises(ValueError, match="divisible"):
        shard_batch(batch, 0, 4)


def test_plan_ddp_validation_errors():
    with pytest.raises(ValueError, match="exclusive"):
        _plan(spmd=True, localities=2).compile()
    with pytest.raises(ValueError, match="grad_codec"):
        _plan(grad_codec="fp16").compile()
    with pytest.raises(ValueError, match="multiple of localities"):
        _plan(localities=2, ddp_shards=3).compile()
    with pytest.raises(ValueError, match="divisible"):
        _plan(ddp_shards=3).compile()        # batch=4, shards=3


def test_multiprocess_modes_refuse_a_driver_on_an_accelerator(monkeypatch):
    """A chip belongs to one process: ddp and spmd name the in-mesh
    strategies instead of spawning children that would need the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="in-mesh strategies"):
        _plan().compile()
    monkeypatch.undo()
    # SPMD starts jax.distributed before any backend: it reads the
    # pinned platform list instead
    old = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", "tpu")
        with pytest.raises(ValueError, match="JAX_PLATFORMS=cpu"):
            _plan(ddp=False, spmd=True, localities=2).compile()
    finally:
        jax.config.update("jax_platforms", old)


def test_make_ddp_step_rejects_unsupported_strategies():
    with pytest.raises(ValueError, match="zero1"):
        steps_lib.make_ddp_step(plan=_plan(strategy=Strategy(name="zero1")))
    with pytest.raises(ValueError, match="grad_accum"):
        steps_lib.make_ddp_step(
            plan=_plan(strategy=Strategy(name="phylanx", grad_accum=2)))


def test_onebit_wire_is_exact_packed_size_for_real_model():
    """Satellite 3 (unit half): for the real test model's gradient plan,
    onebit wire bytes == 1 bit/elem + one f32 scale per 1024 elems,
    EXACTLY - and <= 1/16 of the fp32 wire."""
    step = steps_lib.make_ddp_step(
        shape={"seq_len": 16, "global_batch": 2, "kind": "train"},
        plan=_plan())
    gplan = step.grad_plan
    ob, fp = OneBitCodec().wire_bytes(gplan), Fp32Codec().wire_bytes(gplan)
    expect = sum(bk.size // 8 + 4 * (bk.size // compression.ROW)
                 for bk in gplan.buckets)
    assert ob == expect
    assert 16 * ob <= fp


# -- multi-process drills -----------------------------------------------------

@pytest.mark.multiproc
def test_ddp_fp32_two_localities_bit_identical_to_single():
    """Satellite 2a + 3: with the fp32 codec, a 2-locality DDP run over
    real processes is BIT-IDENTICAL in loss to a single-process run over
    the same 2 batch shards, and the driver's grad_wire_bytes counter is
    EXACTLY steps * (W-1) * codec_bytes."""
    steps = 6
    kw = dict(steps=steps, log_every=2, verbose=False)
    with _plan(ddp_shards=2).compile() as single:
        ref = single.train(**kw)
    with _plan(localities=2, ddp_shards=2).compile() as multi:
        out = multi.train(**kw)
    assert [float(x) for x in out["losses"]] == \
           [float(x) for x in ref["losses"]]
    assert float(out["final_loss"]) == float(ref["final_loss"])
    assert out["codec_bytes"] == ref["codec_bytes"]
    assert ref["grad_wire_bytes"] == 0            # world 1: nothing sent
    assert out["grad_wire_bytes"] == steps * 1 * out["codec_bytes"]


@pytest.mark.multiproc
def test_ddp_onebit_two_localities_converges_with_exact_wire():
    """Satellite 2b + 3: onebit over 2 real processes converges to
    within tolerance of the fp32 reference over 50 steps, with the wire
    EXACTLY the packed size and <= 1/16 of fp32."""
    steps = 50
    kw = dict(steps=steps, log_every=10, verbose=False)
    with _plan(ddp_shards=2).compile() as single:
        ref = single.train(**kw)
    with _plan(localities=2, ddp_shards=2,
               grad_codec="onebit").compile() as multi:
        out = multi.train(**kw)
    assert np.isfinite(out["final_loss"])
    # measured gap at 50 steps is ~0.08; 0.3 bounds run-to-run slack
    assert abs(out["final_loss"] - ref["final_loss"]) < 0.3
    assert out["grad_wire_bytes"] == steps * 1 * out["codec_bytes"]
    assert 16 * out["codec_bytes"] <= ref["codec_bytes"]


@pytest.mark.multiproc
def test_ddp_kill_mid_allreduce_aborts_cleanly():
    """Satellite 4: SIGKILL a worker mid-run - the survivors must abort
    the step with LocalityLostError (no hang) and the session must still
    close cleanly."""
    t0 = time.time()
    with _plan(batch=6, localities=3, ddp_shards=3).compile() as s:
        with pytest.raises(LocalityLostError):
            s.train(steps=30, kill_locality_at_step=3, log_every=10,
                    verbose=False)
    assert time.time() - t0 < 120          # abort, never hang
