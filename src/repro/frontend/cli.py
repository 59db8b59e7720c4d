"""Shared launcher flags: the `--arch/--tiny/--data/--model/--seq/--batch`
block of launch/train.py and launch/serve.py lives here once, and maps 1:1
onto ``Plan`` fields."""
from __future__ import annotations

import argparse
from typing import Optional

from ..configs import ARCH_IDS
from .plan import Plan

__all__ = ["cli_args", "plan_from_args", "serve_flags"]


def cli_args(ap: Optional[argparse.ArgumentParser] = None, *,
             arch_default: Optional[str] = "qwen3-4b", tiny: bool = True,
             mesh: bool = True, batch: Optional[int] = None,
             seq: Optional[int] = None,
             seed: bool = True) -> argparse.ArgumentParser:
    """Add the shared launcher flags to ``ap`` (created if None).  ``batch``
    and ``seq`` are the default values when those flags apply (None omits
    them); ``arch_default=None`` adds ``--arch`` without a default."""
    if ap is None:
        ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=arch_default, choices=ARCH_IDS)
    if tiny:
        ap.add_argument("--tiny", action="store_true", default=True)
        ap.add_argument("--full", dest="tiny", action="store_false")
    if mesh:
        ap.add_argument("--data", type=int, default=1)
        ap.add_argument("--model", type=int, default=1)
        ap.add_argument("--localities", type=int, default=1,
                        help="total process count for the multi-locality "
                             "runtime (1 = in-process)")
        ap.add_argument("--spmd", action="store_true",
                        help="multi-host SPMD mode over jax.distributed "
                             "(needs --localities > 1): every process "
                             "trains in lockstep and checkpoints only "
                             "its addressable shards (DESIGN.md §10)")
        ap.add_argument("--ddp", action="store_true",
                        help="data-parallel training over the "
                             "active-message fabric: each locality "
                             "trains its own batch shards and gradients "
                             "are ring-all-reduced (DESIGN.md §11)")
        ap.add_argument("--grad-codec", dest="grad_codec",
                        default="fp32", choices=("fp32", "onebit"),
                        help="DDP gradient wire codec: fp32 (exact) or "
                             "onebit (1-bit + error feedback, ~1/31 of "
                             "the bytes)")
        ap.add_argument("--ddp-shards", dest="ddp_shards", type=int,
                        default=0,
                        help="batch shard count for --ddp (0 = one per "
                             "locality); must divide --batch and be a "
                             "multiple of --localities")
        ap.add_argument("--elastic", action="store_true",
                        help="elastic membership: accept --join dial-ins "
                             "mid-run, arm the work-stealing loop on "
                             "every locality, and rebalance AGAS objects "
                             "toward newcomers (DESIGN.md §13)")
        ap.add_argument("--elastic-port", dest="elastic_port", type=int,
                        default=0,
                        help="fixed driver listen port for --join "
                             "dialers (0 = ephemeral; printed at start)")
    if seq is not None:
        ap.add_argument("--seq", type=int, default=seq)
    if batch is not None:
        ap.add_argument("--batch", type=int, default=batch)
    if seed:
        ap.add_argument("--seed", type=int, default=0)
    return ap


def serve_flags(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """The serving-gateway flag block (``launch/serve.py``): opt into the
    streamed gateway and its admission-control knobs (DESIGN.md §14)."""
    ap.add_argument("--serve-stream", dest="serve_stream",
                    action="store_true",
                    help="serve through the continuous-batching gateway "
                         "(Session.serve_stream): requests arrive "
                         "mid-flight, prefill state parks in the paged "
                         "inference cache, slot refill loads pages "
                         "instead of recomputing")
    ap.add_argument("--max-inflight", dest="max_inflight", type=int,
                    default=None,
                    help="admission cap on requests holding resources "
                         "(queued-for-a-slot + decoding); default "
                         "2 * slots")
    ap.add_argument("--deadline-ms", dest="deadline_ms", type=float,
                    default=None,
                    help="per-request deadline: a request still short of "
                         "a decode slot when it lapses expires cleanly "
                         "(its node chain is cancelled and its pages "
                         "reclaimed)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="model replica count for --serve-stream "
                         "(DESIGN.md §15): each replica is a prefill/"
                         "decode pair homed on its own locality, and the "
                         "gateway router assigns every request to "
                         "exactly one (page affinity first); streams are "
                         "bit-identical to --replicas 1")
    ap.add_argument("--kill-replica-at", dest="kill_replica_at",
                    default=None, metavar="IDX:ROUND",
                    help="replica-death drill for --serve-stream: mark "
                         "replica IDX dead at decode round ROUND; "
                         "survivors absorb its queued and in-flight "
                         "requests (e.g. 0:2)")
    ap.add_argument("--stats-out", dest="stats_out", default=None,
                    metavar="FILE",
                    help="write the serve summary (gateway counters, "
                         "per-replica split, latency histograms) as JSON "
                         "to FILE - the CI drills assert on it")
    return ap


def plan_from_args(args, **overrides) -> Plan:
    """Build a ``Plan`` from a ``cli_args()`` namespace; keyword overrides
    (e.g. a full ``strategy=Strategy(...)``) win over parsed flags."""
    fields = {name: getattr(args, name)
              for name in ("arch", "tiny", "data", "model", "batch", "seq",
                           "seed", "localities", "spmd", "ddp",
                           "grad_codec", "ddp_shards", "elastic",
                           "elastic_port", "replicas")
              if hasattr(args, name)}
    if hasattr(args, "ckpt"):       # --ckpt -> Plan.ckpt_dir, so worker
        fields["ckpt_dir"] = args.ckpt   # localities get it at spawn
    fields.update(overrides)
    return Plan(**fields)
