"""The training check against faults planted in the program
(``bench/faults.py``), on the CPU at tiny size as data=2 x model=2: each
makes ``correct`` come out false."""
import pytest

from bench_helpers import TRAIN, make_root, run_cell, tiny_config
from test_bench_train import TINY_LIMITS


@pytest.fixture(scope="module")
def train_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("faults"), [
        ("tinyq2.train", tiny_config("qwen2.5-3b"), TRAIN, 4)],
        limits={"tinyq2.train": TINY_LIMITS})


@pytest.mark.parametrize("fault", ["state", "half", "exchange"])
def test_fault_fails_the_training_check(train_root, fault):
    rc, res, err = run_cell(train_root, "tinyq2.train", devices=4,
                            fault=fault)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, err[-2000:]
    assert res["checks"]["change_leaf_gap"]["value"] > \
        TINY_LIMITS["change_leaf_gap"]
