"""Gradient collectives over the active-message fabric (DESIGN.md §11).

True data-parallel training needs an all-reduce that runs on OUR wire -
the TCP active messages of ``messaging.py`` - not on a jax collective
(the CPU backend cannot execute one jit across processes).  This module
provides it as a **ring all-gather with deterministic local combine**:

  * every locality encodes its per-bucket gradient partial with a
    pluggable :class:`GradCodec` and posts one ``grad_ring`` active
    message per bucket to its ring successor;
  * a received segment is stored and *relayed* to the successor until it
    has made ``world - 1`` hops, so after ``world - 1`` relay rounds
    every locality holds every origin's payload;
  * each locality then decodes the contributions and sums them **in
    origin-rank order** - float addition commutes but does not
    associate, so a fixed combine order is what makes every locality
    (and a single-process reference run) produce bit-identical sums.

A reduce-scatter ring would halve the traffic but cannot sum payloads
in the compressed domain (1-bit signs do not add) and sums different
chunks in different rank rotations; the all-gather form keeps the codec
pluggable and the result bitwise reproducible across world sizes.

Codecs (:data:`CODECS`): ``fp32`` ships raw little-endian float32 bucket
bytes (``decode(encode(x))`` is bitwise ``x``); ``onebit`` quantizes
each bucket to sign bits + per-row L1 scales via ``kernels.ops`` (the
``kernels/onebit.py`` Pallas kernels on a TPU), carrying
the persistent per-locality error-feedback residual of
``optim.compression.init_error_state`` across steps - wire cost drops to
1 bit/element plus one float per 1024 elements (~1/31 of fp32).

Failure model: **abort, never hang**.  A peer lost mid-exchange poisons
the ring (``peer_lost``/``abort``); blocked ``allreduce`` calls raise
``LocalityLostError`` and the driver broadcasts ``ddp_abort`` so
survivors with no direct connection to the dead rank abort too
(``distrib.runtime``).  Re-forming the ring is a policy decision left to
a resume run - consistent with the SPMD save-abort story of §10.
"""
from __future__ import annotations

import math
import threading
from typing import Any, Callable, Optional

import jax.numpy as jnp
import numpy as np

from ..analysis import sanitize as _san
from .messaging import Endpoint, PeerLostError

__all__ = ["CODECS", "Fp32Codec", "GradCodec", "OneBitCodec",
           "RingAllReduce", "get_codec"]

#: action name of ring segments on the active-message wire
GRAD_RING_ACTION = "grad_ring"


def _lost_error():
    from .runtime import LocalityLostError   # circular at import time only
    return LocalityLostError


# ---------------------------------------------------------------------------
# Codecs
# ---------------------------------------------------------------------------
class GradCodec:
    """Payload codec for one locality's per-bucket gradient partials.

    A codec turns the fused f32 bucket buffers of a ``FusionPlan``
    (``optim.compression.make_plan``) into wire bytes and back.  It may
    be stateful per locality (the onebit codec owns the error-feedback
    residual); ``reset(plan)`` re-initializes that state at run start.
    ``decode`` must be deterministic - every rank decodes every origin's
    payload with it, and the rank-ordered sum must agree bitwise across
    the world.
    """

    name = "base"

    def reset(self, plan) -> None:
        """(Re-)initialize per-run codec state for ``plan``'s buckets."""

    def encode(self, bufs) -> list[bytes]:
        """f32 bucket buffers -> one wire payload per bucket."""
        raise NotImplementedError

    def decode(self, data: bytes, bucket) -> np.ndarray:
        """One wire payload -> f32[bucket.size] contribution."""
        raise NotImplementedError

    def wire_bytes(self, plan) -> int:
        """Exact payload bytes of one full encode over ``plan`` - the
        number ``grad_wire_bytes`` accounting is asserted against."""
        raise NotImplementedError


class Fp32Codec(GradCodec):
    """Passthrough codec: raw little-endian float32 bucket bytes.

    ``decode(encode(x))`` is bitwise ``x``, which is what makes the
    2-locality fp32 DDP run bit-identical in loss to a single-process
    run over the same batch shards (tests/test_ddp.py parity drill).
    """

    name = "fp32"

    def encode(self, bufs) -> list[bytes]:
        return [np.ascontiguousarray(np.asarray(b, dtype=np.float32))
                .tobytes() for b in bufs]

    def decode(self, data: bytes, bucket) -> np.ndarray:
        return np.frombuffer(data, np.float32)

    def wire_bytes(self, plan) -> int:
        return sum(4 * b.size for b in plan.buckets)


class OneBitCodec(GradCodec):
    """1-bit sign quantization with persistent error feedback.

    Each bucket buffer is viewed as ``[R, 1024]`` (the plan pads buckets
    to a multiple of ``ROW * 32``); the running residual is folded in,
    then ``kernels.ops`` produces the packed sign bitmap, per-row L1
    scales, and the new residual: the ``kernels/onebit.py`` Pallas
    kernels on a TPU, their bit-identical jnp oracle elsewhere.  Wire
    format per bucket: ``size/8`` bytes of little-endian uint32 sign
    words, then ``R`` little-endian float32 scales.  The residual lives on this
    locality only - it is never exchanged or checkpointed, and resets
    with ``reset`` at run (or resume) start.
    """

    name = "onebit"

    def __init__(self):
        self._err: list = []

    def reset(self, plan) -> None:
        from ..optim import compression
        self._err = compression.init_error_state(plan)

    def encode(self, bufs) -> list[bytes]:
        from ..kernels import ops
        from ..optim.compression import ROW
        out = []
        for i, buf in enumerate(bufs):
            g2d = jnp.reshape(jnp.asarray(buf, jnp.float32), (-1, ROW))
            packed, scale, self._err[i] = ops.onebit_quantize(
                g2d, self._err[i], block_rows=_block_rows(g2d.shape[0]))
            # the kernel returns scales lane-replicated [R, 128]; one
            # column is the wire form
            out.append(np.asarray(packed).tobytes()
                       + np.asarray(scale[:, :1]).tobytes())
        return out

    def decode(self, data: bytes, bucket) -> np.ndarray:
        from ..kernels import ops
        from ..optim.compression import ROW
        rows = bucket.size // ROW
        nb = rows * (ROW // 32) * 4
        packed = np.frombuffer(data[:nb], np.uint32).reshape(rows, ROW // 32)
        scale = np.frombuffer(data[nb:], np.float32).reshape(rows, 1)
        deq = ops.onebit_dequantize(
            jnp.asarray(packed),
            jnp.broadcast_to(jnp.asarray(scale), (rows, 128)),
            block_rows=_block_rows(rows))
        return np.asarray(deq).reshape(-1)

    def wire_bytes(self, plan) -> int:
        from ..optim.compression import ROW
        return sum(b.size // 8 + 4 * (b.size // ROW) for b in plan.buckets)


def _block_rows(rows: int) -> int:
    """Kernel row block for a ``[rows, ROW]`` bucket: at most 256 rows
    (about 1 MiB of f32 per operand block, well inside VMEM) and a
    divisor of ``rows``; buckets are padded to multiples of 32 rows."""
    return math.gcd(rows, 256)


CODECS: dict[str, type] = {Fp32Codec.name: Fp32Codec,
                           OneBitCodec.name: OneBitCodec}


def get_codec(name: str) -> GradCodec:
    """A fresh codec instance by name (``fp32`` | ``onebit``).

    Raises:
        ValueError: unknown codec name.
    """
    try:
        return CODECS[name]()
    except KeyError:
        raise ValueError(f"unknown grad codec {name!r} "
                         f"(have: {sorted(CODECS)})") from None


# ---------------------------------------------------------------------------
# Ring all-reduce
# ---------------------------------------------------------------------------
class RingAllReduce:
    """Chunked ring all-reduce of gradient buckets as active messages.

    One instance lives on each locality's endpoint for the process
    lifetime (``Locality``/``DistributedGraph`` construct it so the
    ``grad_ring`` handler exists before any peer can send - posts to an
    unregistered action are dropped, counted in ``unhandled_posts`` and
    warned about, but never delivered late).  ``configure`` arms it
    for one DDP run: it picks the codec, resets codec state, bumps the
    generation (stale segments of an aborted earlier run are dropped by
    generation), and zeroes the per-run ``wire_bytes`` counter.

    Args:
        endpoint: this locality's active-message endpoint (None is
            allowed when ``world == 1`` - nothing crosses the wire).
        world: ring size = total locality count, driver included.
        account: optional callback receiving payload byte counts as they
            are sent (the driver wires this to
            ``DistributedGraph.account_grad_wire_bytes``).
    """

    def __init__(self, endpoint: Optional[Endpoint], world: int, *,
                 account: Optional[Callable[[int], None]] = None):
        self.endpoint = endpoint
        self.world = max(int(world), 1)
        self.rank = endpoint.rank if endpoint is not None else 0
        self.account = account
        self.wire_bytes = 0          # payload bytes sent this run
        self._codec: Optional[GradCodec] = None
        self._plan = None
        self._gen = 0
        self._active = False
        self._dead: Optional[str] = None
        self._cond = threading.Condition()
        # (gen, step, origin, bucket) -> (payload bytes, meta | None)
        self._inbox: dict[tuple, tuple] = {}
        if endpoint is not None:
            endpoint.register(GRAD_RING_ACTION, self._on_seg)

    @property
    def active(self) -> bool:
        """True between ``configure`` and ``deactivate`` - peer loss only
        poisons an active ring."""
        return self._active

    @property
    def gen(self) -> int:
        """Current run generation (segments of earlier gens are dropped)."""
        return self._gen

    # -- run lifecycle -------------------------------------------------------
    def configure(self, codec_name: str, plan, *,
                  gen: Optional[int] = None) -> GradCodec:
        """Arm the ring for one DDP run.

        Args:
            codec_name: a :data:`CODECS` key (``fp32`` | ``onebit``).
            plan: the run's gradient ``FusionPlan`` (every rank must
                build the identical plan from the same ``Plan``).
            gen: explicit generation.  The driver configures first and
                ships its generation in the ``ddp_train`` spec so every
                ring keys segments identically - even a ring on a
                freshly respawned locality, whose local counter restarts
                at 0.  None increments the local counter (driver use).
        Returns:
            The codec instance (with freshly-reset state).
        """
        codec = get_codec(codec_name)
        codec.reset(plan)
        with self._cond:
            if gen is not None and int(gen) < self._gen and _san.active():
                # a regressed generation would resurrect stale inbox
                # segments this ring already agreed to drop (PHY103)
                _san.get().record(
                    "PHY103",
                    f"rank {self.rank}: ring generation regressed "
                    f"{self._gen} -> {int(gen)} in configure()",
                    once_key=f"{self.rank}:{self._gen}:{gen}")
            self._gen = int(gen) if gen is not None else self._gen + 1
            gen = self._gen
            self._inbox = {k: v for k, v in self._inbox.items()
                           if k[0] >= gen}
            self._codec, self._plan = codec, plan
            self._dead = None
            self._active = True
            self.wire_bytes = 0
            self._cond.notify_all()
        return codec

    def deactivate(self):
        """Disarm after a run: later peer losses (normal teardown) no
        longer poison the ring."""
        with self._cond:
            self._active = False

    def abort(self, reason: str):
        """Poison the ring: blocked and future ``allreduce`` calls of
        this generation raise ``LocalityLostError(reason)``."""
        with self._cond:
            if not self._active or self._dead is not None:
                return
            self._dead = str(reason)
            self._cond.notify_all()

    def peer_lost(self, rank: int):
        """Endpoint peer-loss hook: abort the step if a run is active."""
        if self._active:
            self.abort(f"locality {rank} died mid-all-reduce; "
                       f"the step aborted (DESIGN.md §11 failure model)")

    # -- the collective ------------------------------------------------------
    def allreduce(self, step: int, bufs, meta: Any = None, *,
                  timeout: float = 300.0):
        """Sum ``bufs`` (this rank's f32 bucket partials) across the ring.

        Every contribution - this rank's included - passes through the
        codec (``decode(encode(...))``), and the per-bucket sum is
        accumulated in origin-rank order 0..world-1, so all localities
        compute bitwise-identical totals.  The caller divides by its
        shard count; this method only sums.

        Args:
            step: monotone step index (keys segment matching).
            bufs: list of 1-D f32 buffers, one per plan bucket.
            meta: small picklable sidecar (e.g. the shard loss) carried
                on the bucket-0 segment; NOT counted as gradient wire
                bytes.
            timeout: seconds to wait for the other ranks' segments.
        Returns:
            ``(summed_bufs, metas)`` - the rank-ordered per-bucket sums
            (np.float32) and ``{origin_rank: meta}``.
        Raises:
            LocalityLostError: a peer died mid-exchange (ring poisoned).
            TimeoutError: segments missing after ``timeout``.
            RuntimeError: the ring was never ``configure``d.
        """
        with self._cond:
            if self._codec is None:
                raise RuntimeError("RingAllReduce.configure must run "
                                   "before allreduce")
            codec, plan, gen = self._codec, self._plan, self._gen
        payloads = codec.encode(bufs)
        if self.world > 1:
            assert self.endpoint is not None  # world > 1 requires a fabric
            succ = (self.rank + 1) % self.world
            for i, data in enumerate(payloads):
                try:
                    self.endpoint.post(succ, GRAD_RING_ACTION, {
                        "gen": gen, "step": int(step), "origin": self.rank,
                        "hop": 1, "bucket": i, "data": data,
                        "meta": meta if i == 0 else None})
                except PeerLostError as e:
                    self.abort(f"locality {succ} died mid-all-reduce "
                               f"at step {step}: {e}")
                    break
                self._count(len(data))
            need = [(gen, int(step), o, i)
                    for o in range(self.world) if o != self.rank
                    for i in range(len(payloads))]
            with self._cond:
                ok = self._cond.wait_for(
                    lambda: self._dead is not None
                    or all(k in self._inbox for k in need),
                    timeout)
                if self._dead is not None:
                    raise _lost_error()(
                        f"all-reduce at step {step}: {self._dead}")
                if not ok:
                    missing = [k for k in need if k not in self._inbox]
                    raise TimeoutError(
                        f"all-reduce at step {step}: {len(missing)} "
                        f"segment(s) missing after {timeout}s "
                        f"(first: origin {missing[0][2]} bucket "
                        f"{missing[0][3]})")
                got = {o: [self._inbox.pop((gen, int(step), o, i))
                           for i in range(len(payloads))]
                       for o in range(self.world) if o != self.rank}
        else:
            got = {}
        acc: list = [None] * len(payloads)
        metas: dict[int, Any] = {}
        for origin in range(self.world):          # fixed combine order
            if origin == self.rank:
                datas, metas[origin] = payloads, meta
            else:
                datas = [d for d, _ in got[origin]]
                metas[origin] = got[origin][0][1]
            for i, data in enumerate(datas):
                dec = codec.decode(data, plan.buckets[i])
                acc[i] = dec.copy() if acc[i] is None else acc[i] + dec
        return acc, metas

    # -- wire handler --------------------------------------------------------
    def _on_seg(self, src: int, msg: dict):
        key = (msg["gen"], msg["step"], msg["origin"], msg["bucket"])
        with self._cond:
            if msg["gen"] < self._gen:
                return                             # stale run: drop
            self._inbox[key] = (msg["data"], msg.get("meta"))
            self._cond.notify_all()
        if msg["hop"] < self.world - 1:            # relay around the ring
            assert self.endpoint is not None  # world > 1 requires a fabric
            succ = (self.rank + 1) % self.world
            fwd = dict(msg, hop=msg["hop"] + 1)
            try:
                self.endpoint.post(succ, GRAD_RING_ACTION, fwd)
            except PeerLostError as e:
                self.abort(f"locality {succ} died relaying step "
                           f"{msg['step']}: {e}")
                return
            self._count(len(msg["data"]))

    def _count(self, n: int):
        with self._cond:
            self.wire_bytes += int(n)
        if self.account is not None:
            self.account(int(n))
