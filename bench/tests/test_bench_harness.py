"""The harness on the CPU at tiny size: a cell added as new files alone,
the refusals, and the check failing for the lower-precision control and
for a fault planted in the program."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench_helpers import REPO, SERVE, make_root, run_cell, tiny_config

sys.path.insert(0, str(REPO / "bench"))
import serve  # noqa: E402


# at this tiny size the program's mean gap reads 0 to 3.4e-4 and the fp8
# control's 0.012 to 0.015 (seeds 5 to 7 on the CPU)
TINY_LIMIT = 0.003


@pytest.fixture(scope="module")
def serve_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("serve"), [
        ("tinyq3.chat", tiny_config("qwen3-4b"), SERVE, 1)],
        limits={"tinyq3.chat": {"mean_logit_gap": TINY_LIMIT,
                                "short_streams": 0, "failed_requests": 0}})


def test_new_cell_from_files_alone(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files and entries run with no edit to any file the benchmark has."""
    root = make_root(tmp_path, [("throwaway.burst", tiny_config("qwen3-4b"),
                                 dict(SERVE, rate_per_s=30.0), 1)])
    (root / "bench" / "metrics" / "requests_per_s.py").write_text(
        "def read(rec):\n"
        "    return rec['attempted'] / rec['window'].seconds\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append({
        "name": "requests_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.1, "source": "host_clock",
        "workloads": ["throwaway.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, res, err = run_cell(root, "throwaway.burst", seconds=1.0)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    sent = len(serve.arrival_offsets(30.0, 1.0, 5))
    assert res["attempted"] == sent
    assert res["metrics"]["requests_per_s"]["value"] == pytest.approx(
        sent, rel=0.05)
    assert {"itl_p95_ms", "setup_s"} <= set(res["metrics"])
    assert list(res)[-1] == "checks"
    assert res["device"]["memory_peak_bytes"] >= 0


def test_traced_run_reports_per_layer_metrics(serve_root):
    rc, res, err = run_cell(serve_root, "tinyq3.chat", seconds=1.0, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    # no device planes on the CPU: the device metrics stay silent
    assert "device_idle.chat" not in res["metrics"]
    assert res["metrics"]["round_ms.chat"]["value"] > 0
    assert 0 < res["metrics"]["mfu.chat"]["value"]
    assert "busy_s" in res["device"] and "window_s" in res["device"]


def test_control_fails_the_check(serve_root):
    """The fp8 control's tokens in the program's place fail the numbers
    and limits that the program's own tokens of the same run pass."""
    rc, res, err = run_cell(serve_root, "tinyq3.chat", control=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert res["checks"]["mean_logit_gap"]["value"] > TINY_LIMIT
    line = next(x for x in err.splitlines() if x.startswith("reference:"))
    program = json.loads(line.split(" program ", 1)[1])
    assert program["mean_logit_gap"] <= TINY_LIMIT


def test_altered_token_fails_the_check(serve_root):
    rc, res, err = run_cell(serve_root, "tinyq3.chat", fault="token")
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert res["checks"]["mean_logit_gap"]["value"] > \
        res["checks"]["mean_logit_gap"]["limit"]


def test_refuses_a_cpu_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "bench" / "run.py"),
                        "--workload", "qwen3-4b.chat", "--seed", "1",
                        "--seconds", "1"], env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_an_unknown_device_kind(monkeypatch):
    sys.path.insert(0, str(REPO / "bench"))
    import common

    class Dev:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(common.jax, "devices", lambda *a: [Dev()])
    with pytest.raises(SystemExit, match="not in bench/peaks.json"):
        common.require_device(1, common.load_peaks(REPO))


def test_refuses_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ runs nothing."""
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen3-4b.chat", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
