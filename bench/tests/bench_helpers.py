"""Shared by the benchmark's CPU tests: a throwaway checkout root holding a
copy of ``bench/`` and tiny cells, and a runner that drives ``run.py`` in a
child process on the CPU (optionally with a fault planted in the program)."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
TINY = {"hidden_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
        "vocab_size": 512, "num_hidden_layers": 2}
SERVE = {"kind": "serve", "rate_per_s": 20.0, "prompt_len": 16,
         "gen_len": 8, "slots": 4, "warmup_requests": 6,
         "check_tokens": 24}
TRAIN = dict(json.loads((REPO / "bench" / "traffic" / "train-2x2.json")
                        .read_text()), batch=4, seq=32)
CHECKS = {"serve": {"mean_logit_gap": 1.0, "short_streams": 0,
                    "failed_requests": 0},
          "train": json.loads((REPO / "bench" / "checks" /
                               "qwen2.5-3b.train-2x2.json").read_text())}


def tiny_config(name: str) -> dict:
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())
    cfg.update(TINY)
    return cfg


def make_root(tmp: pathlib.Path, cells: list, *, limits=None) -> pathlib.Path:
    """A checkout with ``bench/`` copied, ``src`` linked and a
    BENCHMARK.json of ``cells``: (workload, config dict, traffic dict,
    chips).  Every cell gets every metric the repository's benchmark
    names for the traffic's kind."""
    root = tmp / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(REPO / "src", root / "src")
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = {k: real[k] for k in ("command", "paths", "run_seconds")}
    bench.update(configs=[], workloads=[], end_to_end=[], per_layer=[])
    e2e = {"serve": ["itl_p95_ms"], "train": ["train_tok_s"]}
    per = {"serve": ["round_ms.chat", "mfu.chat", "device_idle.chat"],
           "train": ["mfu.train", "device_idle.train", "comm_ms.train"]}
    byname = {e["name"]: e for e in real["end_to_end"] + real["per_layer"]}
    names = [w for w, *_ in cells]
    for e in real["end_to_end"]:
        if "workloads" not in e:
            bench["end_to_end"].append(dict(e))
    for w, cfg, traffic, chips in cells:
        cname, tname = w.split(".", 1)
        (root / "bench" / "configs" / f"{cname}.json").write_text(
            json.dumps(cfg))
        (root / "bench" / "traffic" / f"{tname}.json").write_text(
            json.dumps(traffic))
        lim = (limits or {}).get(w)
        if lim is None:
            lim = CHECKS[traffic["kind"]]
        (root / "bench" / "checks" / f"{w}.json").write_text(json.dumps(lim))
        bench["configs"].append({"name": cname, "source": "test",
                                 "file": f"bench/configs/{cname}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": w, "config": cname,
                                   "traffic": tname, "chips": chips,
                                   "why": "test"})
        for key, table in (("end_to_end", e2e), ("per_layer", per)):
            for n in table.get(traffic["kind"], []):
                have = {e["name"]: e for e in bench[key]}
                if n not in have:
                    have[n] = dict(byname[n], workloads=[])
                    bench[key].append(have[n])
                have[n]["workloads"].append(w)
    assert len(set(names)) == len(names)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def run_cell(root: pathlib.Path, workload: str, *, seed: int = 5,
             seconds: float = 1.0, trace: int = 0, control: int = 0,
             fault: str = "", devices: int = 1, timeout: float = 600):
    """Drive ``bench/run.py`` in a child process on the CPU, its device
    check replaced by a stand-in for one TPU v5e per CPU device.  Returns
    (exit code, the parsed result line or None, stderr)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("PYTHONPATH", None)
    cmd = [sys.executable, str(pathlib.Path(__file__).with_name(
        "bench_drive.py")), str(root), fault, "--workload", workload,
        "--seed", str(seed), "--seconds", str(seconds), "--trace",
        str(trace), "--control", str(control)]
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=timeout)
    lines = p.stdout.strip().splitlines()
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return p.returncode, result, p.stderr
