"""The training cell's harness on the CPU at tiny size, on four virtual
devices as data=2 x model=2: the cell runs, its traced run reads its
per-layer metrics, and the fp8 control fails the check."""
import pytest

from bench_helpers import TRAIN, make_root, run_cell, tiny_config

# the numbers of this tiny cell on the CPU, seeds 5-7: the program reads a
# gradient gap of 0.0007 to 0.0019, a change gap of 0.011 to 0.013 and a
# global norm gap of 2.3e-5 to 3.6e-4; the fp8 control (seeds 5, 6)
# 0.0047 to 0.0055 in the first and 0.0030 to 0.0067 in the norm; the
# faults of test_bench_train_faults.py 0.10 to 1.0 in the gradient and
# 0.22 to 1.0 in the change.  The same numbers as the chip's check.
TINY_LIMITS = {"grad_leaf_gap": 0.003, "change_leaf_gap": 0.1,
               "grad_norm_rel_gap": 0.0015, "nonfinite_steps": 0}


@pytest.fixture(scope="module")
def train_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("train"), [
        ("tinyq2.train", tiny_config("qwen2.5-3b"), TRAIN, 4)],
        limits={"tinyq2.train": TINY_LIMITS})


def test_train_cell_runs_and_is_correct(train_root):
    rc, res, err = run_cell(train_root, "tinyq2.train", devices=4)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, err[-3000:]
    assert set(res["metrics"]) == {"train_tok_s", "setup_s"}
    assert res["metrics"]["train_tok_s"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert list(res["checks"]) == list(TINY_LIMITS)
    window = next(x for x in err.splitlines() if x.startswith("window:"))
    assert "compiles in window 0" in window


def test_traced_train_run_reads_per_layer_metrics(train_root):
    rc, res, err = run_cell(train_root, "tinyq2.train", devices=4, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True
    # no device planes on the CPU: only the host's share of the peak
    assert set(res["metrics"]) == {"mfu.train"}
    assert 0 < res["metrics"]["mfu.train"]["value"] < 100


def test_control_fails_the_training_check(train_root):
    rc, res, err = run_cell(train_root, "tinyq2.train", devices=4,
                            control=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False
    assert res["checks"]["grad_norm_rel_gap"]["value"] > \
        TINY_LIMITS["grad_norm_rel_gap"]
