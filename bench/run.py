"""The on-chip benchmark: one cell, one run, one process.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``, and this file names no
traffic kind and no architecture:

  configuration  the cell's configuration file (Hugging Face ``config.json``
                 keys).  Its ``"reference"`` key names the plain reference,
                 ``bench/<reference>.py`` (``reference`` where absent), which
                 provides ``dims``, ``make_weights`` and ``plan_overrides``;
  traffic        ``bench/traffic/<traffic>.json``.  Its ``kind`` names the
                 runner, ``bench/<kind>.py``, which provides
                 ``run(cell, args, clock) -> (rec, readings)`` and
                 ``roofline_s(cell, rec, peak)``; its ``data`` and ``model``
                 keys, where given, are the program's mesh (1 x 1 otherwise);
  metrics        the reader of each metric, ``bench/metrics/<name>.py`` (or
                 of the longest dotted prefix of the name that has one:
                 ``mfu.chat`` is read by ``mfu.py``);
  check          ``bench/checks/<workload>.json``, which names the numbers
                 compared and gives each its limit.

So a new architecture, traffic kind or metric is new files and entries.

The runner sets up, warms up every shape it will use, measures for
``--seconds``, then checks what the timed path produced against the plain
reference.  With ``--trace 0`` the result line holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window.  The lines before it (on stderr) describe set-up, the
window and the check; the last of them give each compared number beside
its limit.

``--control 1`` puts the lower-precision control (the reference in fp8) in
the program's place for the check, by the same numbers and limits, so
``correct`` has to come out false.  The program's own readings are printed
beside it.

The run exits non-zero with no result line when JAX finds no TPU, when the
device's kind is not in ``bench/peaks.json``, or when there are fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402

import common  # noqa: E402
from common import log  # noqa: E402


def module(root: pathlib.Path, name: str, what: str):
    """``bench/<name>.py`` of the checkout at ``root``, imported once as
    module ``name``."""
    path = (root / "bench" / f"{name}.py").resolve()
    have = sys.modules.get(name)
    if have is not None and getattr(have, "__file__", None) and \
            pathlib.Path(have.__file__).resolve() == path:
        return have
    if not path.is_file():
        raise SystemExit(f"bench: no {what} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    def __init__(self, root: pathlib.Path, bench: dict, name: str, seed: int):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json "
                             f"(have {sorted(by_name)})")
        w = by_name[name]
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
        self.name, self.chips = name, int(w["chips"])
        self.config = json.loads((root / conf["file"]).read_text())
        self.reference = module(root, self.config.get("reference",
                                                      "reference"),
                                "reference")
        self.dims = self.reference.dims(self.config)
        self.traffic = json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        self.runner = module(root, self.traffic["kind"], "traffic kind")
        self.limits = json.loads(
            (root / "bench" / "checks" / f"{name}.json").read_text())
        self.seed = seed
        # the program's own seed: PRNGKey and numpy take it as 32 bits
        self.program_seed = seed % (2 ** 31)
        self.e2e = _metrics_of(bench["end_to_end"], name)
        self.per_layer = _metrics_of(bench["per_layer"], name)

    def plan(self, **extra):
        """The program's ``Plan`` for this configuration, every size set
        from the configuration file, on the traffic's mesh."""
        from repro.frontend import Plan
        return Plan(arch=self.config["arch"], tiny=False,
                    seed=self.program_seed,
                    data=int(self.traffic.get("data", 1)),
                    model=int(self.traffic.get("model", 1)),
                    overrides=self.reference.plan_overrides(self.config),
                    **extra)


def _metrics_of(entries: list, cell: str) -> list:
    return [e for e in entries if cell in e.get("workloads", [cell])]


def reader(root: pathlib.Path, name: str):
    """The ``read`` function of metric ``name``: ``bench/metrics/<name>.py``
    or the file of its longest dotted prefix."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        path = root / "bench" / "metrics" / (".".join(parts[:k]) + ".py")
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench_metric_{'_'.join(parts[:k])}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise SystemExit(f"bench: no reader for metric {name!r} under "
                     f"bench/metrics/")


def main(argv=None, root: pathlib.Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = Cell(root, bench, args.workload, args.seed)
    peaks = common.load_peaks(root)
    device = common.require_device(cell.chips, peaks)
    peak = peaks[device["kind"]]
    from repro.launch.mesh import use_compile_cache
    log(f"compile-cache: {use_compile_cache()}")
    # every program goes to the cache, however fast it compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = common.CompileClock()
    rec, readings = cell.runner.run(cell, args, clock)
    rec["setup_s"] = rec["window"].t_open - T_START
    rec["roofline_s"] = cell.runner.roofline_s(cell, rec, peak)
    rec["cell"] = cell
    log(f"setup_s {rec['setup_s']:.3f}: compiles {clock.compiles} "
        f"({clock.seconds:.1f} s), persistent-cache hits {clock.cache_hits}")
    entries = cell.per_layer if args.trace else cell.e2e
    metrics = {}
    for e in entries:
        v = reader(root, e["name"])(rec)
        if v is not None:
            metrics[e["name"]] = {"value": float(v), "unit": e["unit"]}
    device["memory_peak_bytes"] = rec["memory_peak_bytes"]
    result = {"attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device}
    tr = rec["window"].trace
    if args.trace and tr:
        busy = [d["busy_s"] for d in tr["devices"].values()]
        device["busy_s"] = sum(busy) / max(len(busy), 1)
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"],
                               "idle_gaps": tr["idle_gaps"]}
        log(f"trace: {json.dumps(tr)}")
    # the numbers the cell's checks file names are compared; one that
    # could not be read (no sample, no step) fails
    checks = {k: (readings[k], lim) for k, lim in cell.limits.items()}
    correct = all(v is not None and v <= lim for v, lim in checks.values())
    result = {"correct": bool(correct), **result,
              "checks": {k: {"value": v, "limit": lim}
                         for k, (v, lim) in checks.items()}}
    for k, (v, lim) in checks.items():
        log(f"check: {k} {v} limit {lim}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
