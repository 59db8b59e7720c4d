"""The trace reduction on a small trace recorded on a TPU v5e: one jitted
bf16 reduction of a 2048 x 2048 matrix run five times inside the
``bench.window`` span, each run inside a ``bench.submit`` span."""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
import trace_reduce  # noqa: E402

TRACE = HERE / "data" / "probe.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_profile(ProfileData.from_file(str(TRACE)))


def test_window_and_busy_time(reduced):
    assert reduced["window_s"] == pytest.approx(0.054991057)
    dev = reduced["devices"]
    assert list(dev) == ["/device:TPU:0"]
    # the device's clock runs ~1 ms behind the host's in this trace, so the
    # first of the five runs falls before the window: four runs of
    # ~90.1 us of fusion plus their tiny copies are inside it
    assert dev["/device:TPU:0"]["busy_s"] == pytest.approx(360.5e-6, rel=1e-3)


def test_breakdown(reduced):
    name, secs = reduced["top_ops"][0]
    assert name == "convert_reduce_fusion"
    assert secs == pytest.approx(4 * 90.1e-6, rel=1e-2)
    assert len(reduced["top_ops"]) <= 10 and len(reduced["idle_gaps"]) <= 10
    idle = sum(s for _, s in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - 360.5e-6, rel=1e-3)
    # the host was mostly sleeping between runs, outside any span
    assert {n for n, _ in reduced["idle_gaps"]} & {"none", "bench.submit"}


def test_interval_arithmetic():
    u = trace_reduce.union([[5, 7], [0, 2], [1, 3], [6, 9]])
    assert u == [[0, 3], [5, 9]]
    assert trace_reduce.length(u) == 7
    assert trace_reduce.subtract([[0, 10]], [[2, 3], [5, 7]]) == \
        [[0, 2], [3, 5], [7, 10]]
    assert trace_reduce.subtract([[0, 4], [6, 8]], [[3, 7]]) == \
        [[0, 3], [7, 8]]
    assert trace_reduce.op_name("%all-reduce.3 = f32[] all-reduce(%x)") == \
        "all-reduce.3"
