"""Training launcher: a thin argparse shim over ``frontend.Plan/Session``.

The loop itself - config -> mesh -> strategy -> shard_map train step ->
synthetic pipeline w/ prefetch -> async checkpoints -> resilience ->
restart - lives in ``frontend/plan.py`` (``Session.train``); this module
only maps flags onto a ``Plan``.

Fault tolerance drill (used by examples/elastic_restart.py and tests):
  * --fail-at-step N     raises mid-run AFTER checkpoints exist (simulated
                         node loss);
  * rerunning with --resume picks up the latest checkpoint - including onto
    a different --data/--model mesh AND a different --localities count
    (elastic restart via checkpoint resharding: with --localities N each
    locality writes/reads its own shards, DESIGN.md §10);
  * --resilience replay  wraps the step in HPX-style replay (retry on
    non-finite results); replicate votes across replicas by checksum;
  * --spmd (with --localities N) runs the multi-host SPMD drill: all N
    processes join one jax.distributed world, train in lockstep, and
    each writes only the addressable shards of the global persistence
    view at every checkpoint (DESIGN.md §10) - a later --resume run with
    any process count reads them back.

Data parallelism over our own fabric (DESIGN.md §11):
  * --ddp (with --localities N) splits the batch into --ddp-shards row
    shards (default: one per locality); every process trains its own
    block and gradients are summed by a ring all-reduce of active
    messages - with --grad-codec onebit the wire carries 1-bit signs +
    error feedback (~1/31 of fp32 bytes), and the exact payload count
    is printed as the report's `grad-wire` line.

Elastic scale-out (DESIGN.md §13):
  * --elastic (optionally --elastic-port P) starts an elastic driver:
    it prints its join address and accepts new localities mid-run;
  * --join HOST:PORT turns THIS invocation into a dial-in locality of
    that driver instead of a training run: it registers, steals host
    tasks the moment it is idle, and exits when the driver's run ends.

Example:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --tiny \
      --steps 30 --batch 8 --seq 64 --strategy phylanx --ckpt /tmp/ck
"""
from __future__ import annotations

import argparse
import json

from repro.core.steps import Strategy
from repro.frontend import cli_args, plan_from_args
from repro.launch.mesh import use_compile_cache


class _StallHook:
    """Driver-side 250 ms stall at one step: the joined locality drains
    its queue, goes hungry, and the next steerable prefetch build is
    diverted to it - the deterministic steal window the churn tests use
    (DESIGN.md §13)."""

    def __init__(self, at: int):
        self.at = at

    def on_step(self, it, metrics):
        if it == self.at:
            import time
            time.sleep(0.25)


def run(args) -> dict:
    if getattr(args, "join", None):
        # this process is a dial-in locality, not a training driver
        from repro.distrib import join_locality
        host, _, port = args.join.rpartition(":")
        rank = join_locality((host or "127.0.0.1", int(port)))
        print(f"[train] served as elastic locality {rank}; driver run "
              f"ended", flush=True)
        return {"joined_as": rank}
    strategy = Strategy(name=args.strategy, grad_accum=args.grad_accum,
                        sequence_parallel=args.seq_parallel)
    plan = plan_from_args(args, strategy=strategy, remat=args.remat)
    with plan.compile() as session:
        if session.join_address is not None:
            host, port = session.join_address
            print(f"[train] elastic: accepting --join {host}:{port}",
                  flush=True)
        if getattr(args, "expect_joins", 0):
            # drill determinism: a --join dialer pays its own Python/JAX
            # startup, so hold the train loop until it is a member -
            # otherwise a fast driver finishes before the dial lands
            import time as _time
            deadline = _time.monotonic() + 180.0
            while (session.distributed.stats()["joined_localities"]
                   < args.expect_joins):
                if _time.monotonic() > deadline:
                    raise TimeoutError(
                        f"expected {args.expect_joins} --join dial-in(s) "
                        f"within 180s")
                _time.sleep(0.1)
            print(f"[train] elastic: {args.expect_joins} dial-in(s) "
                  f"joined; training", flush=True)
        hooks = None
        if getattr(args, "stall_at_step", None) is not None:
            hooks = _StallHook(args.stall_at_step)
        out = session.train(
            steps=args.steps, hooks=hooks,
            ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
            log_every=args.log_every, resume=args.resume,
            fail_at_step=args.fail_at_step,
            kill_locality_at_step=args.kill_locality_at_step,
            resilience=args.resilience)
    if getattr(args, "stats_out", None):
        # machine-readable summary for drills/CI: loss trajectory plus
        # the distributed counters (stolen_tasks, migrated_objects...)
        with open(args.stats_out, "w") as f:
            json.dump({"final_loss": out["final_loss"],
                       "losses": out["losses"], "step": out["step"],
                       "distributed": out["runtime_stats"].get(
                           "distributed")}, f, indent=2)
    return out


def parser() -> argparse.ArgumentParser:
    ap = cli_args(seq=64, batch=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--strategy", default="phylanx",
                    choices=["phylanx", "horovod", "zero1", "onebit"])
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--kill-locality-at-step", type=int, default=None,
                    help="drill: SIGKILL a worker locality at this step "
                         "(needs --localities > 1); training must survive")
    ap.add_argument("--resilience", default="none",
                    choices=["none", "replay", "replicate"])
    ap.add_argument("--join", default=None, metavar="HOST:PORT",
                    help="join a running --elastic driver as an extra "
                         "locality instead of training (all other flags "
                         "are ignored; the driver ships its config)")
    ap.add_argument("--stats-out", dest="stats_out", default=None,
                    metavar="FILE",
                    help="write a JSON summary (losses + distributed "
                         "counters) here after training")
    ap.add_argument("--expect-joins", dest="expect_joins", type=int,
                    default=0, metavar="N",
                    help="drill (needs --elastic): wait for N --join "
                         "dial-ins before the first step so the joiner "
                         "is a member for the whole run")
    ap.add_argument("--stall-at-step", dest="stall_at_step", type=int,
                    default=None, metavar="K",
                    help="drill: sleep 250 ms on the driver at step K - "
                         "the deterministic work-steal window")
    return ap


if __name__ == "__main__":
    use_compile_cache()
    run(parser().parse_args())
