"""The serving cells: an open-loop generator feeding the gateway live.

One ``Session.serve_stream(queue=RequestQueue())`` call serves the whole
run (it draws the weights once).  A feeder thread feeds it while the main
thread sits in that call:

  warm-up   ``warmup_requests`` requests through the same gateway, each
            sent once the one before it has its first token, so that they
            join a fresh batch and a running one; then until every one is
            done.  Every program the window runs is then compiled and
            loaded;
  window    ``seconds`` of open-loop arrivals at ``rate_per_s``.  The gaps
            between arrivals are the quantiles of an exponential
            distribution, shuffled by the seed: every seed offers the same
            number of requests with the same gaps, in another order;
  close     waits for every request that arrived in the window, at most
            a minute plus ``seconds`` (the cell runs below its knee).

Every token is timed at the client: each handle's token list is replaced at
submit by one that stamps ``time.perf_counter()`` on every append.  A
request is timed from the moment it was due, not from when the generator
got round to it, and the generator's lateness is reported, as are the
Python garbage collections from the window's opening to the close (a pause
of the whole process shows in every stream).

``bench/sweep.py`` offers its rates with the same ``arrival_offsets``,
``offer``, ``drain`` and ``timings``.
"""
from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

from common import Window, compile_free_window, host_span


class TimedTokens(list):
    """A token list that stamps the client's clock on every append."""

    def __init__(self):
        super().__init__()
        self.times: list[float] = []

    def append(self, tok):
        self.times.append(time.perf_counter())
        super().append(tok)


def arrival_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Seconds after the window opens at which each request is due.

    ``round(rate * seconds)`` requests; the gaps are the midpoint quantiles
    of Exp(rate), scaled to fill the window and shuffled by the seed."""
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps = gaps / gaps.sum() * seconds
    gaps = np.random.default_rng(seed).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def prompts_for(seed: int, n: int, length: int, vocab: int) -> np.ndarray:
    return np.random.default_rng(seed + 1).integers(
        0, vocab, (n, length)).astype(np.int32)


def offer(queue, t_open: float, offsets, prompts) -> list[dict]:
    """Submit each prompt when it is due, ``t_open`` plus its offset, with
    its tokens timed at the client; the requests as they were sent."""
    reqs = []
    for off, prompt in zip(offsets, prompts):
        due = t_open + off
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        with host_span("bench.submit"):
            h = queue.submit(prompt)
            h.tokens = TimedTokens()
        reqs.append({"due": due, "sent": time.perf_counter(),
                     "prompt": prompt, "handle": h})
    return reqs


def drain(reqs: list, deadline: float) -> bool:
    """Wait until every request has ended or ``deadline`` has passed;
    whether all ended."""
    for r in reqs:
        try:
            r["handle"].result(timeout=max(0.0, deadline - time.perf_counter()))
        except Exception:  # noqa: BLE001 - its status says what happened
            pass
    return all(r["handle"].done() for r in reqs)


def timings(reqs: list, lo: float, hi: float) -> dict:
    """What the client saw of ``reqs`` (``offer``'s records) in the window
    ``[lo, hi]``: each request's time to first token, every gap between
    two tokens of one request that ends in it, and the tokens in it."""
    times = [list(getattr(r["handle"].tokens, "times", [])) for r in reqs]
    return {"ttft_s": [ts[0] - r["due"] for r, ts in zip(reqs, times) if ts],
            "itl_s": [b - a for ts in times for a, b in zip(ts, ts[1:])
                      if lo <= b <= hi],
            "out_tokens": sum(lo <= x <= hi for ts in times for x in ts)}


class GcPauses:
    """Python's garbage collections while it is installed: (start, seconds,
    generation) each."""

    def __init__(self):
        self.pauses: list = []
        self._t0 = 0.0

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def _on(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t0 = now
        else:
            self.pauses.append((self._t0, now - self._t0, info["generation"]))


def warm_up(queue, prompts) -> list:
    """Send each prompt once the one before it has its first token, then
    wait for all: the first joins a fresh batch, the others a running one,
    and each finishes, so every program of the window is compiled."""
    handles = []
    for p in prompts:
        if handles:
            while not handles[-1].tokens and not handles[-1].done():
                time.sleep(0.005)
        handles.append(queue.submit(p))
    for h in handles:
        h.result(timeout=600)
    return handles


class ServeRun:
    """One serving run: set-up, warm-up, window, close; the records the
    metric readers take."""

    def __init__(self, cell, seconds: float, trace: bool, clock):
        self.cell, self.seconds, self.trace, self.clock = (
            cell, seconds, trace, clock)
        self.t = cell.traffic
        self.error: BaseException | None = None
        self.window: Window | None = None
        self.requests: list[dict] = []
        self.warmup: list = []
        self.gc = GcPauses()

    # -- the feeder thread ---------------------------------------------------
    def _drive(self, session, queue):
        try:
            self._drive_inner(session, queue)
        except BaseException as e:  # noqa: BLE001 - re-raised by run()
            self.error = e
        finally:
            queue.close()

    def _drive_inner(self, session, queue):
        t, cell = self.t, self.cell
        vocab = cell.dims["V"]
        n_warm = int(t["warmup_requests"])
        warm = prompts_for(cell.seed + 7919, n_warm, t["prompt_len"], vocab)
        with host_span("bench.warmup"):
            self.warmup = warm_up(queue, warm)
        offsets = arrival_offsets(float(t["rate_per_s"]), self.seconds,
                                  cell.seed)
        prompts = prompts_for(cell.seed, len(offsets), t["prompt_len"], vocab)
        stats = lambda: dict(session.runtime.stats().serve)
        with self.gc:
            with compile_free_window(self.clock, self.trace, stats) as win:
                self.window = win
                self.requests = offer(queue, win.t_open, offsets, prompts)
                rest = win.t_open + self.seconds - time.perf_counter()
                if rest > 0:
                    with host_span("bench.wait"):
                        time.sleep(rest)
            drain(self.requests, time.perf_counter() + 60.0 + self.seconds)

    # -- the whole run -------------------------------------------------------
    def run(self, session) -> dict:
        from repro.frontend.gateway import RequestQueue
        t = self.t
        queue = RequestQueue()
        feeder = threading.Thread(target=self._drive, args=(session, queue),
                                  name="bench-feeder", daemon=True)
        feeder.start()
        out = session.serve_stream(
            queue=queue, prompt_len=int(t["prompt_len"]),
            gen_len=int(t["gen_len"]), slots=int(t["slots"]), verbose=False)
        feeder.join()
        if self.error is not None:
            raise self.error
        return self.records(out)

    def records(self, out) -> dict:
        t, win = self.t, self.window
        lo, hi = win.t_open, win.t_close
        reqs = []
        for r in self.requests:
            h = r["handle"]
            reqs.append({"due": r["due"], "late_s": r["sent"] - r["due"],
                         "status": h.status,
                         "times": list(getattr(h.tokens, "times", [])),
                         "tokens": list(h.tokens), "prompt": r["prompt"]})
        seen = timings(self.requests, lo, hi)
        # decode tokens in the window and the position each was made at
        positions = [t["prompt_len"] + j - 1 for r in reqs
                     for j, x in enumerate(r["times"]) if j and lo <= x <= hi]
        prefills = sum(1 for r in reqs if r["times"] and lo <= r["times"][0] <= hi)
        failed = sum(r["status"] in ("failed", "expired", "rejected")
                     for r in reqs)
        return {
            "kind": "serve", "window": win, "requests": reqs,
            "attempted": len(reqs), "failed": failed,
            "completed": sum(r["status"] == "done" for r in reqs),
            **seen, "decode_positions": positions, "prefills": prefills,
            "stalls": stalls(reqs, self.gc.pauses, lo, hi),
            "late_s": [r["late_s"] for r in reqs],
            "warmup_done": sum(h.status == "done" for h in self.warmup),
            "gateway": {k: out[k] for k in ("completed", "cancelled",
                                            "expired", "failed", "rejected")},
        }


def stalls(reqs: list, pauses: list, lo: float, hi: float) -> dict:
    """Where the streams stood still: the longest gap between two tokens of
    one request that ends in the window (its ms, its start after the
    opening, and the garbage collection inside it), the generator's worst
    lateness, and the garbage collections that began in the window."""
    gap = max(((b - a, a) for r in reqs for a, b in zip(r["times"],
                                                       r["times"][1:])
               if lo <= b <= hi), default=(0.0, lo))
    late = max(((r["late_s"], r["due"]) for r in reqs), default=(0.0, lo))
    overlap = lambda a, b: sum(max(0.0, min(b, s + d) - max(a, s))
                               for s, d, _ in pauses)
    mine = [(d, g) for s, d, g in pauses if lo <= s <= hi]
    return {"longest_gap_ms": gap[0] * 1e3, "at_s": gap[1] - lo,
            "gc_ms_in_it": overlap(gap[1], gap[1] + gap[0]) * 1e3,
            "late_max_ms": late[0] * 1e3, "late_at_s": late[1] - lo,
            "gc_in_window": len(mine),
            "gc_ms": sum(d for d, _ in mine) * 1e3,
            "gc_longest_ms": max((d for d, _ in mine), default=0.0) * 1e3,
            "gc_gen2": sum(g == 2 for _, g in mine)}


def check_sample(rec: dict, seed: int, tokens_wanted: int) -> list[dict]:
    """Finished window requests to compare with the reference, drawn from
    the seed: enough of them for ``tokens_wanted`` served tokens."""
    done = [r for r in rec["requests"] if r["status"] == "done"]
    if not done:
        return []
    per = max(len(done[0]["tokens"]), 1)
    k = min(len(done), max(1, math.ceil(tokens_wanted / per)))
    idx = np.random.default_rng(seed + 17).choice(len(done), k, replace=False)
    return [done[i] for i in sorted(idx)]
