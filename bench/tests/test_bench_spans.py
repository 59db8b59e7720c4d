"""The readers of the program's counters, and device time per jitted
program with the host-device clock skew, on the CPU."""
import json
import pathlib
import sys

import pytest

from bench_helpers import REPO, SERVE, make_root, run_cell, tiny_config

sys.path.insert(0, str(REPO / "bench"))
import trace_modules  # noqa: E402

TRACE = pathlib.Path(__file__).resolve().parent / "data" / "probe.xplane.pb"
NEW = ("join_ms.chat", "page_ms.chat", "host_mb.chat")


@pytest.fixture(scope="module")
def probe():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(TRACE))


def test_modules_inside_the_window(probe):
    """Five runs of one jitted reduction; the device's clock runs ~1 ms
    behind the host's, so the first falls before the window."""
    mods = trace_modules.modules(probe, trace_modules.window(probe))
    assert list(mods) == ["jit__lambda"]
    assert mods["jit__lambda"]["runs"] == 4
    assert mods["jit__lambda"]["seconds"] == pytest.approx(4 * 90.13e-6,
                                                           rel=1e-3)


def test_clock_skew_by_run_id_and_by_order(probe):
    skew = trace_modules.clock_skew_us(probe)
    # device start minus DoEnqueueProgram: -1147 to -1159 us over the runs
    assert skew == pytest.approx(-1154.0, abs=5.0)
    runs = [(0, 7), (100, 8), (250, 9)]
    launches = [(1000, 9), (900, 8), (800, 7), (990, 7)]
    assert trace_modules.skew_us(runs, launches) == pytest.approx(-0.8)
    # without run ids the k-th run goes with the k-th launch
    assert trace_modules.skew_us([(t, None) for t, _ in runs],
                                 [(t, None) for t, _ in launches[:3]]) \
        == pytest.approx(-0.8)
    assert trace_modules.skew_us([], launches) is None


def test_traced_run_reports_join_and_paging_metrics(tmp_path):
    root = make_root(tmp_path, [("tinyq3.chat", tiny_config("qwen3-4b"),
                                 SERVE, 1)])
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"] += [dict(e, workloads=["tinyq3.chat"])
                           for e in real["per_layer"] if e["name"] in NEW]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, res, err = run_cell(root, "tinyq3.chat", seconds=1.0, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    m = res["metrics"]
    assert set(NEW) <= set(m), sorted(m)
    assert m["join_ms.chat"]["value"] > 0
    assert m["page_ms.chat"]["value"] > 0
    # the tiny cell's decode state: 2 layers x (k, v) x 2 kv heads x 32 x
    # (16 + 8) positions in bf16, out and back once per join
    state = 2 * 2 * 2 * 32 * 24 * 2
    assert m["host_mb.chat"]["value"] == pytest.approx(2 * state / 1e6)
    # no device planes on the CPU: nothing reads device time per program
    assert "device_idle.chat" not in m
