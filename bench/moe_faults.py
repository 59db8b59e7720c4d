"""Faults planted in the program's MoE layer, to show what the training
check makes of them: ``plant(name)`` patches the program before a cell is
set up.

  routed_scale  the routed experts' weights are not scaled by
                ``routed_scale`` (2.446 for Moonlight);
  shared        the shared experts add nothing;
  capacity      the capacity of the old ``sort`` engine: each expert keeps
                its first ``moe.capacity(T, E, k, 1.25)`` assignments in
                token order and the rest are dropped.

    python3 bench/moe_faults.py --fault <name> <bench/calibrate.py arguments>

plants the fault, then reads the cell's compared numbers on each seed as
``bench/calibrate.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys

FAULTS = ("routed_scale", "shared", "capacity")


def plant(name: str):
    import jax
    import jax.numpy as jnp
    from repro.models import moe
    if name == "routed_scale":
        real_route = moe.route
        moe.route = lambda x, p, top_k, scoring, routed_scale: real_route(
            x, p, top_k, scoring, 1.0)
    elif name == "shared":
        moe._shared = lambda x, p: jnp.zeros_like(x)
    elif name == "capacity":
        real_sort = moe._dispatch_sort

        def capped(x, p, gate_w, gate_i, offset):
            T, k = gate_i.shape
            E = p["router"].shape[-1]
            hot = jax.nn.one_hot(gate_i.reshape(-1), E, dtype=jnp.int32)
            rank = jnp.sum((jnp.cumsum(hot, 0) - hot) * hot, -1)
            keep = (rank < moe.capacity(T, E, k, 1.25)).reshape(T, k)
            return real_sort(x, p, jnp.where(keep, gate_w, 0.0), gate_i,
                             offset)
        moe._dispatch_sort = capped
    elif name:
        raise SystemExit(f"unknown MoE fault {name!r} (have {FAULTS})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fault", required=True, choices=FAULTS)
    args, rest = ap.parse_known_args(argv)
    import calibrate          # sets up sys.path, as the benchmark's entry
    plant(args.fault)
    print(json.dumps({"moe_fault": args.fault}), flush=True)
    return calibrate.main(rest)


if __name__ == "__main__":
    sys.exit(main())
