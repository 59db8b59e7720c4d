"""The on-chip benchmark: one cell, one run, one process.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration file, its traffic mix ``bench/traffic/<traffic>.json`` (whose
``kind`` picks the runner; ``serve`` is the one there is), the reader of
each metric ``bench/metrics/<name>.py`` (or of the longest dotted prefix of
the name that has one: ``mfu.chat`` is read by ``mfu.py``), and the limits
of its correctness check ``bench/checks/<workload>.json`` (which names the
numbers compared and gives each its limit).

The run sets up, warms up every shape it will use, measures for
``--seconds``, then checks what the timed path produced against the plain
reference in ``bench/reference.py``.  With ``--trace 0`` the result line
holds the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from a profiler trace of the window.  The lines before it (on
stderr) describe set-up, the window and the check; the last of them give
each compared number beside its limit.

``--control 1`` puts the lower-precision control in the program's place
for the check: at each position of the same prompts and served tokens, the
token that the reference computed in fp8 puts first is judged instead of
the served one, by the same numbers and limits, so ``correct`` has to come
out false.  The program's own readings are printed beside it.

The run exits non-zero with no result line when JAX finds no TPU, when the
device's kind is not in ``bench/peaks.json``, or when there are fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402

import common  # noqa: E402
import flops  # noqa: E402
import reference  # noqa: E402
from common import log  # noqa: E402


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
        self.dims = reference.dims(self.config)
        self.traffic = json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        self.limits = json.loads(
            (root / "bench" / "checks" / f"{name}.json").read_text())
        self.seed = seed
        # the program's own seed: PRNGKey and numpy take it as 32 bits
        self.program_seed = seed % (2 ** 31)
        self.e2e = _metrics_of(bench["end_to_end"], name)
        self.per_layer = _metrics_of(bench["per_layer"], name)

    def plan(self, **extra):
        """The program's ``Plan`` for this configuration, every size set
        from the configuration file."""
        from repro.frontend import Plan
        m = self.dims
        over = {"n_layers": m["L"], "d_model": m["d"], "n_heads": m["H"],
                "n_kv_heads": m["Hkv"], "head_dim": m["hd"], "d_ff": m["ff"],
                "vocab": m["V"], "rope_theta": m["theta"],
                "qk_norm": m["qk_norm"], "qkv_bias": m["bias"]}
        return Plan(arch=self.config["arch"], tiny=False,
                    seed=self.program_seed, overrides=over, **extra)


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


def serve_roofline_s(cell: Cell, rec: dict, peak: dict) -> float:
    """Least device seconds the window's prefills and decode rounds need."""
    m, t, win = cell.dims, cell.traffic, rec["window"]
    rounds = (win.delta("real_tokens") + win.delta("padded_slot_tokens")) \
        / t["slots"]
    pf, bw = peak["bf16_flops_per_s"], peak["hbm_bytes_per_s"]
    fd, bd = flops.decode(m, rounds, rec["decode_positions"])
    fp, bp = flops.prefill(m, int(t["prompt_len"]))
    return (max(fd / pf, bd / bw)
            + rec["prefills"] * max(fp / pf, bp / bw)) / cell.chips


def _mean(xs) -> float | None:
    return float(sum(xs) / len(xs)) if len(xs) else None


def gap_readings(gaps: list) -> dict:
    return {"mean_logit_gap": _mean(gaps),
            "max_logit_gap": float(max(gaps)) if gaps else None}


def run_serve(cell: Cell, args, clock) -> tuple:
    from serve import ServeRun, check_sample
    session = cell.plan().compile()
    try:
        rec = ServeRun(cell, args.seconds, bool(args.trace), clock).run(session)
    finally:
        session.close()
    rec["setup_s"] = rec["window"].t_open - T_START
    rec["memory_peak_bytes"] = common.memory_peak(cell.chips)
    # free the program's state before the reference runs on the chip
    session._gateway = None
    del session
    gc.collect()
    log(f"live_bytes[after the program]: {common.live_bytes()}")
    t = cell.traffic
    log(f"window: {rec['window'].seconds:.3f} s, {rec['attempted']} requests "
        f"due, {rec['completed']} completed, {rec['failed']} failed; "
        f"gateway {json.dumps(rec['gateway'])}; compiles in window "
        f"{rec['window'].compiles}; generator late p50 "
        f"{common.percentile(rec['late_s'], 50, 1e3)} ms")
    for name, key, ps in (("ttft", "ttft_s", (50, 75, 90, 95, 100)),
                          ("itl", "itl_s", (50, 90, 95, 99, 99.9, 100))):
        qs = " ".join(f"p{p:g} {common.percentile(rec[key], p, 1e3)}"
                      for p in ps)
        mean = _mean(rec[key])
        log(f"tails: {name} ms {qs} mean "
            f"{None if mean is None else mean * 1e3} (n={len(rec[key])})")
    log(f"stalls: {json.dumps(rec['stalls'])}")
    log(f"out tokens in the window {rec['out_tokens']}")
    # correctness: the served tokens against the reference's logits
    sample = check_sample(rec, cell.seed, int(t["check_tokens"]))
    t0 = time.perf_counter()
    w = reference.make_weights(cell.config, cell.program_seed)
    served, ctl = [], []
    for r in sample:
        g, c = reference.served_gaps(cell.config, w, r["prompt"],
                                     r["tokens"], control=args.control)
        served += list(g)
        ctl += [] if c is None else list(c)
    del w
    log(f"reference: {len(sample)} requests, {len(served)} served tokens "
        f"compared in {time.perf_counter() - t0:.1f} s; program "
        f"{json.dumps(gap_readings(served))}")
    if args.control:
        log(f"control: the fp8 reference's tokens in the program's place "
            f"{json.dumps(gap_readings(ctl))}")
    short = sum(len(r["tokens"]) != t["gen_len"] + 1
                for r in rec["requests"] if r["status"] == "done")
    readings = {**gap_readings(ctl if args.control else served),
                "short_streams": short, "failed_requests": rec["failed"]}
    return rec, readings


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
    kind = cell.traffic["kind"]
    if kind != "serve":
        raise SystemExit(f"bench: unknown traffic kind {kind!r}")
    rec, readings = run_serve(cell, args, clock)
    rec["roofline_s"] = serve_roofline_s(cell, rec, peak)
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
