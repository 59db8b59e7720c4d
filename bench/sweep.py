"""Find a serving cell's knee: the highest offered rate at which completions
keep pace with arrivals, with no growing backlog.

    python3 bench/sweep.py --workload <name> --rates 0.25,0.5,1 --seconds 40

One process and one gateway call serve every rate in turn (the weights are
drawn once): a warm-up, then for each rate, lowest first, the run's own
measured window (``common.compile_free_window``) of open-loop arrivals made
and timed by ``bench/serve.py``'s generator.  Each rate prints one line:
requests offered and completed inside the window, the backlog left at its
close, the compiles inside it, the time to first token of requests due in
the window's first and second half (a backlog that grows shows as a second
half much slower than the first), and the decode rounds, slot tokens and
refills the gateway counted in the window.  Below the knee the rate's requests are drained
before the next rate; at the first rate whose backlog grows, the requests
still in flight are cancelled and the sweep stops.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import common
from run import ROOT, Cell
from serve import (arrival_offsets, drain, offer, prompts_for, timings,
                   warm_up)

DRAIN_S = 120.0


def grows(first: list, second: list) -> bool:
    """A backlog that grows: the second half's median time to first token
    is over a second and more than twice the first half's."""
    a = common.percentile(first, 50)
    b = common.percentile(second, 50)
    return bool(b is not None and b > 1.0 and (a is None or b > 2 * a))


def sweep(cell, session, queue, rates, seconds, clock):
    t = cell.traffic
    vocab = cell.dims["V"]
    stats = lambda: dict(session.runtime.stats().serve)
    warm = prompts_for(cell.seed + 7919, int(t["warmup_requests"]),
                       t["prompt_len"], vocab)
    warm_up(queue, warm)
    for i, rate in enumerate(rates):
        offs = arrival_offsets(rate, seconds, cell.seed + i)
        prompts = prompts_for(cell.seed + i, len(offs), t["prompt_len"],
                              vocab)
        with common.compile_free_window(clock, False, stats) as win:
            reqs = offer(queue, win.t_open, offs, prompts)
            time.sleep(max(0.0, win.t_open + seconds - time.perf_counter()))
        t0, t1 = win.t_open, win.t_close
        done_in = sum(r["handle"].done() for r in reqs)
        d = {k: win.delta(k)
             for k in ("real_tokens", "padded_slot_tokens", "refills")}
        rounds = (d["real_tokens"] + d["padded_slot_tokens"]) / t["slots"]
        half = t0 + seconds / 2
        first = timings([r for r in reqs if r["due"] < half], t0, t1)
        second = timings([r for r in reqs if r["due"] >= half], t0, t1)
        over = grows(first["ttft_s"], second["ttft_s"])
        row = {"rate": rate, "offered": len(reqs),
               "completed_in_window": done_in,
               "backlog_at_close": len(reqs) - done_in,
               "ttft_p50_ms_first_half":
                   common.percentile(first["ttft_s"], 50, 1e3),
               "ttft_p50_ms_second_half":
                   common.percentile(second["ttft_s"], 50, 1e3),
               "offered_tok_s": rate * (t["gen_len"] + 1),
               "out_tok_s": (first["out_tokens"] + second["out_tokens"])
               / win.seconds,
               "rounds": rounds,
               "round_ms": win.seconds * 1e3 / rounds if rounds else None,
               "real_tokens": d["real_tokens"], "refills": d["refills"],
               "compiles_in_window": win.compiles, "grows": over}
        if not over:
            over = not drain(reqs, time.perf_counter() + DRAIN_S)
            row["drain_timed_out"] = over
            row["drain_s"] = time.perf_counter() - t1
        print(json.dumps(row), flush=True)
        if over:
            for r in reqs:
                r["handle"].cancel()
            drain(reqs, time.perf_counter() + DRAIN_S)
            return


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = Cell(ROOT, bench, args.workload, args.seed)
    common.require_device(cell.chips, common.load_peaks(ROOT))
    from repro.frontend.gateway import RequestQueue
    from repro.launch.mesh import use_compile_cache
    use_compile_cache()
    clock = common.CompileClock()
    rates = sorted(float(r) for r in args.rates.split(","))
    t = cell.traffic
    queue, err = RequestQueue(), []

    def drive():
        try:
            sweep(cell, session, queue, rates, args.seconds, clock)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            err.append(e)
        finally:
            queue.close()
    with cell.plan().compile() as session:
        th = threading.Thread(target=drive, daemon=True)
        th.start()
        session.serve_stream(queue=queue, prompt_len=int(t["prompt_len"]),
                             gen_len=int(t["gen_len"]),
                             slots=int(t["slots"]), verbose=False)
        th.join()
    if err:
        raise err[0]
    return 0


if __name__ == "__main__":
    sys.exit(main())
