"""Serving gateway battery (DESIGN.md §14): continuous batching with
mid-flight arrivals and the paged inference cache, plus fault injection
(cancel mid-decode, deadline expiry mid-prefill, poisoned prefill) and
the 2-locality parity / kill-locality drills.

The load-bearing property: prefill runs ONCE per request (at admission,
batch=1) and decode math is row-independent, so a request's token stream
depends only on its prompt.  Every fault test asserts the survivors'
streams are *bit-identical* to an unperturbed run AND that the faulted
request's slot and pages were reclaimed (``pages_live == 0``)."""
import threading
import time
from concurrent.futures import CancelledError

import numpy as np
import pytest

from repro.analysis import sanitize
from repro.frontend import Plan
from repro.frontend.gateway import (DeadlineExpired, RequestQueue,
                                    RequestRejected)

ARCH = "qwen2.5-3b"
ARRIVALS = (0, 0, 1, 3, 3)           # staggered, requests > slots


def _plan(**kw):
    kw.setdefault("arch", ARCH)
    return Plan(**kw)


def _kwargs(**over):
    kw = dict(prompt_len=16, gen_len=4, slots=2, verbose=False)
    kw.update(over)
    return kw


def _stream(trace, **over):
    """One fresh-session gateway run over a deterministic trace."""
    with _plan().compile() as session:
        return session.serve_stream(trace=trace, **_kwargs(**over))


_BASELINES: dict = {}


def _baseline_streams(arrivals, **over):
    """Unperturbed streams for an arrival script (cached per config):
    rid numbering and prompts depend only on entry order and plan.seed,
    so a fault run over the same script is directly comparable."""
    key = (tuple(arrivals), tuple(sorted(over.items())))
    if key not in _BASELINES:
        out = _stream([{"at_round": r} for r in arrivals], **over)
        assert out["completed"] == len(arrivals)
        _BASELINES[key] = out["streams"]
    return _BASELINES[key]


# -- the tentpole: mid-flight arrivals, zero prefill recomputation -----------

def test_streamed_arrivals_complete_with_zero_prefill_recompute():
    with _plan().compile() as session:
        out = session.serve_stream(
            trace=[{"at_round": r} for r in ARRIVALS], **_kwargs())
        assert session.lint() == []          # live gateway graph is clean
    n, gen = len(ARRIVALS), 4
    assert out["completed"] == n
    assert out["cancelled"] == out["expired"] == out["failed"] == 0
    # every stream: the prefill token plus gen_len decoded tokens
    assert sorted(out["streams"]) == [f"r{i}" for i in range(n)]
    assert all(len(s) == gen + 1 for s in out["streams"].values())
    assert out["tokens"] == n * gen

    # the paged-cache contract: every slot join loaded pages; the prefill
    # recompute fallback never ran; everything was reclaimed
    serve = out["runtime_stats"]["serve"]
    assert serve["refills"] == serve["page_hits"] == n
    assert serve.get("prefill_recompute", 0) == 0
    cache = out["cache"]
    assert cache["cache_puts"] == cache["cache_hits"] == n
    assert cache["pages_live"] == 0 and cache["cache_entries"] == 0
    assert cache["page_allocs"] == cache["page_frees"]

    # staggered arrivals mean epochs were cut mid-run, not one big wave
    assert out["epochs"] >= 2
    names = set(out["nodes"])
    for i in range(n):
        assert {f"stack:r{i}", f"prefill:r{i}", f"finish:r{i}",
                f"request:r{i}"} <= names
    assert "refill:e0" in names and "decode:e0:t0" in names

    # latency histograms: every phase observed, counts match the run
    hist = out["runtime_stats"]["request_latency_hist"]
    assert hist["edges_s"] and len(hist["labels"]) == len(hist["edges_s"]) + 1
    counts = hist["counts"]
    assert sum(counts["queue_wait"]) == n
    assert sum(counts["prefill"]) == n
    assert sum(counts["total"]) == n
    assert sum(counts["decode_token"]) == n * gen

    # padded-slot accounting: real + padded covers every (round, slot)
    assert serve["real_tokens"] == n * gen
    assert serve["real_tokens"] + serve["padded_slot_tokens"] \
        == out["rounds"] * 2
    assert out["padded_tokens"] == serve["padded_slot_tokens"]


def test_gateway_trace_builder_matches_live_run():
    """phylint's static mirror (analysis.gateway_trace) and the live
    gateway build the same tree: same names, lanes and edges."""
    from repro.analysis import gateway_trace

    out = _stream([{"at_round": r} for r in ARRIVALS])
    sig = out["trace"]
    live = {(name, lane, tuple(sig[d][0] for d in deps))
            for name, lane, deps in sig}
    g = gateway_trace(_plan(), requests=len(ARRIVALS), gen_len=4, slots=2,
                      arrivals=list(ARRIVALS))
    mirror = {(n.name, n.lane, tuple(g.nodes[d].name for d in n.deps))
              for n in g.nodes}
    assert live == mirror


# -- fault injection ---------------------------------------------------------

def test_cancel_mid_decode_reclaims_slot_and_preserves_survivors():
    base = _baseline_streams(ARRIVALS)
    trace = [{"at_round": r} for r in ARRIVALS]
    trace[1]["cancel_after"] = 2             # r1: cancel after 2 tokens
    out = _stream(trace)
    assert out["cancelled"] == 1 and out["completed"] == len(ARRIVALS) - 1
    h = next(h for h in out["handles"] if h.rid == "r1")
    assert h.status == "cancelled"
    with pytest.raises(CancelledError):
        h.result(timeout=5)
    assert len(h.tokens) == 1 + 2            # prefill + the 2 decoded
    assert out["streams"]["r1"] == base["r1"][:3]   # a prefix, not junk
    for rid, stream in base.items():         # survivors are bit-identical
        if rid != "r1":
            assert out["streams"][rid] == stream
    assert out["cache"]["pages_live"] == 0
    assert out["cache"]["cache_entries"] == 0


def test_deadline_expiry_while_waiting_for_a_slot():
    """slots=1: r0 monopolizes the slot; r1 is admitted (prefill runs,
    pages park) but its deadline lapses before a slot frees - it must
    expire cleanly with its pages reclaimed, and r0 is untouched."""
    kw = dict(gen_len=8, slots=1)
    base = _baseline_streams((0,), **kw)
    out = _stream([{"at_round": 0}, {"at_round": 0, "deadline_ms": 50}],
                  **kw)
    assert out["completed"] == 1 and out["expired"] == 1
    h = next(h for h in out["handles"] if h.rid == "r1")
    assert h.status == "expired"
    with pytest.raises(DeadlineExpired):
        h.result(timeout=5)
    assert out["streams"]["r0"] == base["r0"]
    assert out["cache"]["pages_live"] == 0
    assert out["cache"]["cache_entries"] == 0
    assert out["runtime_stats"]["serve"].get("prefill_recompute", 0) == 0


def test_poisoned_prefill_is_contained_to_its_chain():
    base = _baseline_streams(ARRIVALS)
    trace = [{"at_round": r} for r in ARRIVALS]
    trace[2]["inject"] = "poison-prefill"
    out = _stream(trace)
    assert out["failed"] == 1 and out["completed"] == len(ARRIVALS) - 1
    h = next(h for h in out["handles"] if h.rid == "r2")
    assert h.status == "failed"
    with pytest.raises(RuntimeError, match="injected prefill poison"):
        h.result(timeout=5)
    assert h.tokens == []                    # never reached a slot
    for rid, stream in base.items():         # the poison never crossed
        if rid != "r2":                      # into the shared decode chain
            assert out["streams"][rid] == stream
    assert out["cache"]["pages_live"] == 0


def test_fault_battery_drains_cleanly_under_sanitizer():
    """All three faults in one run with the concurrency sanitizer armed:
    the gateway must drain without a deadlock diagnostic (every promise
    is producer-backed and resolved, even for killed chains)."""
    trace = [{"at_round": r} for r in ARRIVALS]
    trace[1]["cancel_after"] = 1
    trace[2]["inject"] = "poison-prefill"
    trace[4]["deadline_ms"] = 0.0            # expires before admission
    with sanitize.enabled():
        out = _stream(trace)
        assert sanitize.get().diagnostics() == []
    assert out["completed"] == 2
    assert out["cancelled"] == out["expired"] == out["failed"] == 1
    assert out["cache"]["pages_live"] == 0
    statuses = {h.rid: h.status for h in out["handles"]}
    assert statuses == {"r0": "done", "r1": "cancelled", "r2": "failed",
                        "r3": "done", "r4": "expired"}


# -- the live side: threads, admission, rejection ----------------------------

def test_live_queue_submissions_from_another_thread():
    with _plan().compile() as session:
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, session.cfg.vocab, 16).astype(np.int32)
                   for _ in range(3)]
        q = RequestQueue()

        def feeder():
            for p in prompts:
                q.submit(p)
                time.sleep(0.02)
            q.close()

        t = threading.Thread(target=feeder)
        t.start()
        out = session.serve_stream(queue=q, **_kwargs())
        t.join()
    assert out["completed"] == 3
    assert all(h.result(timeout=5) == out["streams"][h.rid]
               for h in out["handles"])


def test_request_queue_backlog_and_close_reject():
    q = RequestQueue(max_queue=1)
    ok = q.submit([1, 2])
    full = q.submit([3, 4])
    assert ok.status == "queued" and full.status == "rejected"
    with pytest.raises(RequestRejected, match="capacity"):
        full.result(timeout=1)
    q.close()
    late = q.submit([5, 6])
    assert late.status == "rejected"
    with pytest.raises(RequestRejected, match="closed"):
        late.result(timeout=1)
    assert q.submitted == 1 and q.rejected == 2


def test_wave_serve_accounts_padded_slot_compute():
    """``Session.serve`` pads idle slots into every wave; the padded-slot
    compute must be accounted separately, never folded into tokens."""
    with _plan().compile() as session:
        out = session.serve(requests=3, slots=2, prompt_len=16, gen_len=4,
                            verbose=False)
        serve = session.runtime.stats().serve
    assert out["tokens"] == 3 * 4            # only real requests
    assert out["padded_tokens"] == 1 * 4     # wave 1 ran a padded slot
    assert serve["real_tokens"] == 12
    assert serve["padded_slot_tokens"] == 4


# -- multi-replica routing (DESIGN.md §15) -----------------------------------

def test_two_replica_streams_bit_identical_to_single_replica():
    """The tentpole contract: N-replica streams are bit-identical per
    request to the 1-replica gateway, with zero cross-replica page
    traffic and zero prefill recompute in steady state."""
    base = _baseline_streams(ARRIVALS)
    out = _stream([{"at_round": r} for r in ARRIVALS], replicas=2)
    assert out["completed"] == len(ARRIVALS)
    assert out["replicas"] == 2
    assert out["streams"] == base

    serve = out["runtime_stats"]["serve"]
    assert serve.get("cross_replica_page_fetches", 0) == 0
    assert serve.get("prefill_recompute", 0) == 0
    assert serve["refills"] == serve["page_hits"] == len(ARRIVALS)

    # the router spread work: both replicas admitted and refilled, and
    # the per-replica counter split covers the flat totals
    per = out["runtime_stats"]["serve_replicas"]
    assert sorted(per) == ["0", "1"]
    assert all(per[k]["refills"] > 0 for k in per)
    assert sum(per[k]["refills"] for k in per) == serve["refills"]
    assigned = out["replica_assignments"]
    assert sorted(assigned) == [f"r{i}" for i in range(len(ARRIVALS))]
    assert set(assigned.values()) == {0, 1}

    # page hygiene across both named caches over the shared pool
    cache = out["cache"]
    assert cache["cache_transfers_in"] == cache["cache_transfers_out"] == 0
    assert cache["pages_live"] == 0 and cache["cache_entries"] == 0
    assert cache["page_allocs"] == cache["page_frees"]

    # namespaced decode chains for both replicas coexist in one graph
    names = set(out["nodes"])
    assert "refill:R0:e0" in names and "refill:R1:e0" in names
    assert not any(n.startswith(("refill:e", "decode:e")) for n in names)


def test_replica_trace_builder_matches_live_run():
    """The static mirror replays the live ReplicaRouter, so the 2-replica
    tree matches the live run node for node (phylint's gate)."""
    from repro.analysis import gateway_trace

    out = _stream([{"at_round": r} for r in ARRIVALS], replicas=2)
    sig = out["trace"]
    live = {(name, lane, tuple(sig[d][0] for d in deps))
            for name, lane, deps in sig}
    g = gateway_trace(_plan(), requests=len(ARRIVALS), gen_len=4, slots=2,
                      arrivals=list(ARRIVALS), replicas=2)
    mirror = {(n.name, n.lane, tuple(g.nodes[d].name for d in n.deps))
              for n in g.nodes}
    assert live == mirror


def test_kill_replica_drill_completes_on_survivor():
    """Replica-death rebalance: kill replica 0 at round 2; the survivor
    adopts its pages (a counted cross-replica fetch, never a prefill
    recompute) and completes every request with bit-identical streams."""
    base = _baseline_streams(ARRIVALS)
    with sanitize.enabled():
        out = _stream([{"at_round": r} for r in ARRIVALS], replicas=2,
                      kill_replica_at_round=(0, 2))
        assert sanitize.get().diagnostics() == []
    assert out["completed"] == len(ARRIVALS)
    assert out["cancelled"] == out["expired"] == out["failed"] == 0
    assert out["streams"] == base

    serve = out["runtime_stats"]["serve"]
    assert serve["replica_deaths"] == 1
    assert serve["replica_migrations"] >= 1
    assert serve["cross_replica_page_fetches"] >= 1
    assert serve.get("prefill_recompute", 0) == 0
    # everything ends routed to the survivor; no pages leak either side
    assert set(out["replica_assignments"].values()) == {1}
    cache = out["cache"]
    assert cache["cache_transfers_in"] == cache["cache_transfers_out"] \
        == serve["cross_replica_page_fetches"]
    assert cache["pages_live"] == 0 and cache["cache_entries"] == 0
    assert cache["page_allocs"] == cache["page_frees"]


def test_kill_last_replica_revives_on_driver():
    """Killing the only replica must not strand the queue: the gateway
    revives it (re-homed on the driver) and completes everything."""
    base = _baseline_streams(ARRIVALS)
    out = _stream([{"at_round": r} for r in ARRIVALS], replicas=1,
                  kill_replica_at_round=(0, 2))
    assert out["completed"] == len(ARRIVALS)
    assert out["streams"] == base
    serve = out["runtime_stats"]["serve"]
    assert serve["replica_deaths"] == 1 and serve["replica_revivals"] == 1
    assert serve.get("prefill_recompute", 0) == 0
    assert out["cache"]["pages_live"] == 0


# -- gateway bugfix sweep: CV wake, submit/close race ------------------------

class _ParkingQueue(RequestQueue):
    """Records every park of the idle gateway: the timeout it waited
    with, and what it found when it woke."""

    def __init__(self):
        super().__init__()
        self.parked = threading.Event()
        self.wakes: list = []

    def wait_nonempty(self, timeout=None):
        self.parked.set()
        woke = super().wait_nonempty(timeout)
        with self._lock:
            self.wakes.append((timeout, len(self._items), self.closed))
        return woke


def test_idle_gateway_wakes_on_submit_without_polling_latency():
    """The idle gateway parks on the queue condition variable (no more
    20 Hz poll): every submission and the close are made to a parked
    gateway, and each wakes it exactly once - no park waits with a poll
    timeout, and no park returns before a submission or close."""
    with _plan().compile() as session:
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, session.cfg.vocab, 16).astype(np.int32)
                   for _ in range(6)]
        q = _ParkingQueue()

        def feeder():
            try:        # submit only to a parked gateway, then close
                for p in prompts:
                    if not q.parked.wait(timeout=300):
                        return
                    q.parked.clear()
                    q.submit(p)
                q.parked.wait(timeout=300)
            finally:
                q.close()

        t = threading.Thread(target=feeder)
        t.start()
        out = session.serve_stream(queue=q, **_kwargs(gen_len=2))
        t.join(timeout=60)
        assert not t.is_alive()
    assert out["completed"] == len(prompts)
    hist = out["runtime_stats"]["request_latency_hist"]
    assert sum(hist["counts"]["queue_wait"]) == len(prompts)
    # one wake per submission (finding exactly that request), then the
    # close; a polling park would show a timeout or an empty wake
    assert q.wakes == ([(None, 1, False)] * len(prompts)
                       + [(None, 0, True)]), q.wakes


def test_submit_racing_close_is_atomic_at_the_queue():
    """Hammer submit() from many threads while close() lands: every
    handle is either queued-before-close (drainable) or deterministically
    rejected - never enqueued into a closed queue, never stranded."""
    for trial in range(25):
        q = RequestQueue()
        handles: list = []
        lock = threading.Lock()
        start = threading.Barrier(5)

        def submitter():
            start.wait()
            for _ in range(20):
                h = q.submit([1, 2, 3])
                with lock:
                    handles.append(h)

        threads = [threading.Thread(target=submitter) for _ in range(4)]
        for t in threads:
            t.start()
        start.wait()
        q.close()
        for t in threads:
            t.join()
        taken = q.take_ready(10**9)
        assert q.drained()
        rejected = [h for h in handles if h.status == "rejected"]
        queued = [h for h in handles if h.status == "queued"]
        # exhaustive: nothing in any third state, nothing left behind
        assert len(rejected) + len(queued) == len(handles)
        assert sorted(h.rid for h in queued) == sorted(h.rid for h in taken)
        assert q.submitted == len(queued) and q.rejected == len(rejected)
        for h in rejected:                   # terminal, not stranded
            assert h.done()
            with pytest.raises(RequestRejected, match="closed|capacity"):
                h.result(timeout=1)


def test_gateway_resolves_every_handle_when_close_races_submit():
    """End to end: a feeder hammers submissions while close() races in;
    the gateway must leave every returned handle terminal (served or
    rejected), with served + rejected == submitted attempts."""
    with _plan().compile() as session:
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, session.cfg.vocab, 16).astype(np.int32)
                   for _ in range(16)]
        q = RequestQueue()
        handles: list = []

        def feeder():
            for i, p in enumerate(prompts):
                handles.append(q.submit(p))
                time.sleep(0.01)

        def closer():
            time.sleep(0.06)             # lands mid-feed: some submits
            q.close()                    # race the close and must reject

        tf, tc = threading.Thread(target=feeder), \
            threading.Thread(target=closer)
        tf.start(), tc.start()
        out = session.serve_stream(queue=q, **_kwargs(gen_len=2))
        tf.join(), tc.join()
    for h in handles:
        assert h.done(), f"{h.rid} stranded in {h.status!r}"
        assert h.status in ("done", "rejected")
    served = sum(1 for h in handles if h.status == "done")
    rejected = sum(1 for h in handles if h.status == "rejected")
    assert served + rejected == len(prompts)
    assert out["completed"] == served and out["rejected"] == rejected
    assert out["cache"]["pages_live"] == 0


# -- multiproc tier: locality parity + kill drill ----------------------------

@pytest.mark.multiproc
def test_two_locality_gateway_streams_match_single_process():
    trace = [{"at_round": r} for r in ARRIVALS]
    with _plan(localities=2).compile() as multi:
        out2 = multi.serve_stream(trace=trace, **_kwargs())
    assert out2["completed"] == len(ARRIVALS)
    assert out2["cache"]["pages_live"] == 0
    base = _baseline_streams(ARRIVALS)       # 1-process, same script
    assert out2["streams"] == base


@pytest.mark.multiproc
def test_kill_locality_mid_stream_completes_survivors():
    """SIGKILL a worker while the gateway is streaming: its in-flight
    stack tasks re-spawn, requests submitted after the kill still
    complete, and every stream matches the 1-process run."""
    kw = dict(gen_len=6)
    with _plan(localities=2).compile() as session:
        rng = np.random.default_rng(23)
        prompts = [rng.integers(0, session.cfg.vocab, 16).astype(np.int32)
                   for _ in range(6)]
        q = RequestQueue()
        killed = {}

        def feeder():
            for i, p in enumerate(prompts):
                if i == 3:
                    killed["rank"] = session.kill_locality()
                q.submit(p)
                time.sleep(0.05)
            q.close()

        t = threading.Thread(target=feeder)
        t.start()
        out = session.serve_stream(queue=q, **_kwargs(**kw))
        t.join()
    assert killed["rank"] is not None
    assert out["completed"] == len(prompts)
    assert out["cache"]["pages_live"] == 0
    base = _stream([{"prompt": p} for p in prompts], **kw)
    assert out["streams"] == base["streams"]


@pytest.mark.multiproc
def test_two_locality_two_replica_streams_match_single_process():
    """2 replicas homed on 2 localities (replica 0 on the worker,
    replica 1 on the driver): streams match the 1-process 1-replica run
    and steady state never crosses replica page boundaries."""
    trace = [{"at_round": r} for r in ARRIVALS]
    with _plan(localities=2, replicas=2).compile() as multi:
        out2 = multi.serve_stream(trace=trace, **_kwargs())
    assert out2["completed"] == len(ARRIVALS)
    assert out2["replicas"] == 2
    assert out2["cache"]["pages_live"] == 0
    serve = out2["runtime_stats"]["serve"]
    assert serve.get("cross_replica_page_fetches", 0) == 0
    assert serve.get("prefill_recompute", 0) == 0
    assert out2["streams"] == _baseline_streams(ARRIVALS)


@pytest.mark.multiproc
def test_kill_locality_retires_its_replica_and_survivor_absorbs():
    """SIGKILL the worker locality hosting replica 0 mid-stream: the
    liveness sweep retires that replica, the driver-homed survivor
    adopts its pages and every request completes bit-identically."""
    with _plan(localities=2, replicas=2).compile() as session:
        rng = np.random.default_rng(31)
        prompts = [rng.integers(0, session.cfg.vocab, 16).astype(np.int32)
                   for _ in range(6)]
        q = RequestQueue()
        killed = {}

        def feeder():
            for i, p in enumerate(prompts):
                if i == 3:
                    killed["rank"] = session.kill_locality()
                q.submit(p)
                time.sleep(0.05)
            q.close()

        t = threading.Thread(target=feeder)
        t.start()
        out = session.serve_stream(queue=q, **_kwargs())
        t.join()
    assert killed["rank"] is not None
    assert out["completed"] == len(prompts)
    serve = out["runtime_stats"]["serve"]
    assert serve["replica_deaths"] == 1
    assert serve.get("prefill_recompute", 0) == 0
    assert out["cache"]["pages_live"] == 0
    base = _stream([{"prompt": p} for p in prompts])
    assert out["streams"] == base["streams"]
