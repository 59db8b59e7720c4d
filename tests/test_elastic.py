"""Elastic membership churn drills (DESIGN.md §13): dial-in/spawned
joins mid-train, work stealing across localities, newcomer loss,
simultaneous join+kill churn, and the concurrent bidirectional dial
regression on the parcel layer.

Every drill runs REAL processes (``multiprocessing.spawn``) and asserts
the elastic machinery never changes *what* is computed - final loss
stays bit-identical to the static reference run - only *where*.
Everything a worker runs must be a module-level function here, because
it crosses the wire by reference.
"""
import os
import threading
import time

import pytest

from repro.distrib import DistributedGraph
from repro.distrib.messaging import Endpoint
from repro.frontend import Plan

ARCH = "qwen2.5-3b"


def _plan(**kw):
    kw.setdefault("arch", ARCH)
    kw.setdefault("batch", 4)
    kw.setdefault("seq", 16)
    return Plan(**kw)


# -- module-level task functions (ship by reference) -------------------------

def nap_id(i, delay=0.05):
    time.sleep(delay)
    return i


def gated_nap_id(i, gate, delay=0.1):
    """``nap_id`` held until the file ``gate`` exists."""
    while not os.path.exists(gate):
        time.sleep(0.01)
    return nap_id(i, delay)


def _assert_procs_reaped(pids, timeout=30.0):
    """Every worker pid must be gone (reaped, not just zombied) soon
    after close - the no-orphans half of the churn acceptance."""
    deadline = time.time() + timeout
    for pid in pids:
        while time.time() < deadline:
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                break                      # exited and reaped
            time.sleep(0.05)
        else:
            pytest.fail(f"worker pid {pid} still alive after close")


class _Churn:
    """Training hook that joins (and optionally kills) localities at
    fixed steps; picklable state never crosses the wire - it drives the
    driver-side session only."""

    def __init__(self, session, join_at, kill_newcomer_at=None,
                 kill_rank_at=None, idle_gap_at=None):
        self.session = session
        self.join_at = join_at
        self.kill_newcomer_at = kill_newcomer_at
        self.kill_rank_at = kill_rank_at or {}   # {step: rank}
        self.idle_gap_at = idle_gap_at
        self.joined_rank = None

    def on_step(self, it, metrics):
        if it == self.idle_gap_at:
            # a deliberate device-step-sized stall: the newcomer drains
            # its queue, goes hungry, and the next steerable prefetch
            # build is diverted to it - the deterministic steal window
            time.sleep(0.25)
        if it in self.kill_rank_at:
            # churn both directions in the same step: SIGKILL an
            # original member WHILE the join handshake runs
            t = threading.Thread(
                target=self.session.kill_locality,
                args=(self.kill_rank_at[it],))
            t.start()
            self.joined_rank = self.session.add_locality()
            t.join(timeout=60)
            assert not t.is_alive()
            return
        if it == self.join_at:
            self.joined_rank = self.session.add_locality()
        if self.kill_newcomer_at is not None \
                and it == self.kill_newcomer_at:
            assert self.joined_rank is not None
            self.session.kill_locality(self.joined_rank)


def _reference_loss(steps):
    with _plan().compile() as single:
        return single.train(steps=steps, log_every=6,
                            verbose=False)["final_loss"]


# -- join mid-train: loss parity + real steals --------------------------------

def test_join_mid_train_matches_reference_and_steals():
    """The acceptance drill: an elastic session that starts alone and
    gains a locality at step 3 finishes with the SAME loss as the
    static single-process run, and the newcomer really pulled work
    (``stolen_tasks > 0``) - stealing moves placement, never values."""
    steps = 14
    ref = _reference_loss(steps)
    with _plan(elastic=True).compile() as ses:
        hooks = _Churn(ses, join_at=3, idle_gap_at=7)
        out = ses.train(steps=steps, log_every=6, hooks=hooks,
                        verbose=False)
        dstats = out["runtime_stats"]["distributed"]
        pids = [p.pid for p in ses.distributed.group.procs.values()]
    assert hooks.joined_rank == 1
    assert out["final_loss"] == pytest.approx(ref, abs=1e-6)
    assert dstats["joined_localities"] == 1
    assert dstats["membership_gen"] >= 1
    assert dstats["stolen_tasks"] > 0
    assert dstats["dispatched"].get(1, 0) > 0    # work really landed there
    _assert_procs_reaped(pids)


# -- join then lose the newcomer ---------------------------------------------

def test_join_then_kill_newcomer_train_survives():
    """A joiner that dies mid-run must cost nothing: its in-flight
    tasks re-spawn (idempotent prefetch builds) and the loss trajectory
    is untouched."""
    steps = 12
    ref = _reference_loss(steps)
    with _plan(elastic=True).compile() as ses:
        hooks = _Churn(ses, join_at=2, kill_newcomer_at=6)
        out = ses.train(steps=steps, log_every=6, hooks=hooks,
                        verbose=False)
        dstats = out["runtime_stats"]["distributed"]
        pids = [p.pid for p in ses.distributed.group.procs.values()]
    assert out["final_loss"] == pytest.approx(ref, abs=1e-6)
    assert dstats["joined_localities"] == 1
    assert dstats["alive_workers"] == []         # the kill really landed
    assert dstats["membership_gen"] >= 2         # one join + one loss
    _assert_procs_reaped(pids)


# -- simultaneous join + kill of an original member ---------------------------

def test_simultaneous_join_and_kill_original_peer():
    """Worst-case churn: at one step an ORIGINAL worker is SIGKILLed
    while a newcomer's join handshake is in flight.  Membership gossip
    is generation-keyed, so both events land, the newcomer becomes the
    only live worker, and the loss still matches the static run."""
    steps = 12
    ref = _reference_loss(steps)
    with _plan(localities=2, elastic=True).compile() as ses:
        hooks = _Churn(ses, join_at=None, kill_rank_at={4: 1})
        out = ses.train(steps=steps, log_every=6, hooks=hooks,
                        verbose=False)
        dstats = out["runtime_stats"]["distributed"]
        pids = [p.pid for p in ses.distributed.group.procs.values()]
    assert hooks.joined_rank == 2
    assert out["final_loss"] == pytest.approx(ref, abs=1e-6)
    assert dstats["alive_workers"] == [2]        # newcomer in, original out
    assert dstats["membership_gen"] >= 2
    assert dstats["joined_localities"] == 1
    _assert_procs_reaped(pids)


# -- steal modes on a bare DistributedGraph -----------------------------------

def test_backlog_steal_after_join_spares_pinned_tasks(tmp_path):
    """Victim-lease stealing: a worker with a deep queue of steerable
    tasks loses some of them to a fresh joiner - but explicitly pinned
    (``locality=``) tasks are never stealable.  The backlog waits on a
    gate opened only after the join, so the joiner always finds it."""
    gate = str(tmp_path / "gate")
    g = DistributedGraph(localities=2, elastic=True)
    try:
        futs = [g.defer(gated_nap_id, i, gate, name=f"p{i}")
                for i in range(24)]
        rank = g.add_locality(timeout=120)
        # the joiner steals within a second or two; the wait stays under
        # the 30 s after which the fabric drops a silent dialed link
        deadline = time.monotonic() + 20
        while g.stolen_tasks == 0 and time.monotonic() < deadline:
            time.sleep(0.02)
        open(gate, "w").close()
        assert [f.result(timeout=120) for f in futs] == list(range(24))
        s = g.stats()
        assert s["stolen_tasks"] > 0
        assert s["dispatched"].get(rank, 0) > 0
        # pinned tasks: park the joiner idle, pin everything to rank 1
        g.stolen_tasks = 0
        futs = [g.defer(nap_id, i, name=f"q{i}", locality=1)
                for i in range(10)]
        assert [f.result(timeout=120) for f in futs] == list(range(10))
        assert g.stolen_tasks == 0
    finally:
        g.shutdown()


def test_rebalance_migrates_objects_and_stale_refs_still_resolve():
    """AGAS rebalance at join: pinned driver objects migrate to the
    newcomer behind forwarding stubs; every stale ``RemoteRef`` held
    from before the join keeps dereferencing to the same value."""
    g = DistributedGraph(localities=1, elastic=True)
    try:
        refs = [g.defer(nap_id, i, delay=0.0, name=f"m{i}",
                        pin=True).result(timeout=60) for i in range(10)]
        assert all(r.owner == 0 for r in refs)
        g.add_locality(timeout=120)
        s = g.stats()
        assert s["migrated_objects"] > 0
        for i, ref in enumerate(refs):           # stale gids: stub-chased
            assert g.fetch(ref) == i
        assert g.directory.audit()["forwarded_fetches"] > 0
    finally:
        g.shutdown()


# -- parcel layer: concurrent bidirectional dial ------------------------------

def test_concurrent_bidirectional_dial_is_one_connection():
    """Two endpoints dialing each other at the same instant (both sides
    of a join racing) must converge on ONE logical connection: requests
    flow both ways afterwards and closing the loser socket never fires
    a spurious peer-lost."""
    for _ in range(8):                           # the race needs attempts
        a, b = Endpoint(0), Endpoint(1)
        lost = []
        a.on_peer_lost = lost.append
        b.on_peer_lost = lost.append
        a.register("ping", lambda src, p: ("a", p))
        b.register("ping", lambda src, p: ("b", p))
        gate = threading.Barrier(2)

        def dial(ep, rank, addr):
            gate.wait()
            ep.connect(rank, addr)

        ts = [threading.Thread(target=dial, args=(a, 1, b.address)),
              threading.Thread(target=dial, args=(b, 0, a.address))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=40)
            assert not t.is_alive()
        deadline = time.time() + 10
        while time.time() < deadline and not (a.peers() == [1]
                                              and b.peers() == [0]):
            time.sleep(0.01)
        assert a.peers() == [1] and b.peers() == [0]
        assert a.request(1, "ping", 7, timeout=30) == ("b", 7)
        assert b.request(0, "ping", 8, timeout=30) == ("a", 8)
        time.sleep(0.2)       # give a dying duplicate time to misfire
        assert lost == []
        a.close()
        b.close()
