"""Read a cell's compared numbers on many seeds in one process, for
setting the limits of its checks file:

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--seconds 1] [--control 1] [--fault half]

Each seed runs as ``bench/run.py`` would (set-up, warm-up, a window of
``--seconds``, the check), in one process, so the programs compile once.
``--control 1`` reads the lower-precision control in the program's place;
``--fault`` plants a fault of ``bench/faults.py`` in the program.  One JSON
line per seed on stdout: the seed and the numbers read.  Nothing here is a
measurement of speed.
"""
from __future__ import annotations

import argparse
import json
import sys

import run  # sets up sys.path, as the benchmark's entry point
import common
import faults


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = run.Cell(run.ROOT, bench, args.workload, seeds[0])
    common.require_device(cell.chips, common.load_peaks(run.ROOT))
    from repro.launch.mesh import use_compile_cache
    use_compile_cache()
    run.jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = common.CompileClock()
    faults.plant(args.fault)
    for seed in seeds:
        cell = run.Cell(run.ROOT, bench, args.workload, seed)
        opts = argparse.Namespace(seconds=args.seconds, trace=0,
                                  control=args.control)
        rec, readings = cell.runner.run(cell, opts, clock)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "control": args.control, "readings": readings,
                          "program": rec.get("readings"),
                          "compiles_in_window": rec["window"].compiles}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
