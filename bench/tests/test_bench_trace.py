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


def test_collective_ops_by_name():
    names = {"all-reduce-start.3": True, "all-reduce-done.3": True,
             "all-reduce.7": True, "all-gather-start": True,
             "all-gather.2": True, "reduce-scatter.1": True,
             "collective-permute-done.4": True, "all-to-all.9": True,
             "fusion.12": False, "reduce.5": False, "while.3": False,
             "copy-start.2": False, "convert_reduce_fusion": False,
             "multiply_reduce_fusion.13": False, "all-reducer": False}
    assert {n: trace_reduce.is_collective(n) for n in names} == names


def test_leaves_drop_enclosing_ops():
    # a while loop enclosing two ops, then one op on its own
    ivs = [[0, 10], [1, 3], [4, 9], [12, 15]]
    assert trace_reduce.leaves(ivs) == [[1, 3], [4, 9], [12, 15]]


def test_modules_and_clock_skew(reduced):
    """The jitted reduction's four runs inside the window, and the skew's
    median beside its least difference."""
    mods = reduced["modules"]
    assert list(mods) == ["jit__lambda"] and mods["jit__lambda"]["runs"] == 4
    skew = reduced["clock_skew_us"]
    assert skew["median"] == pytest.approx(-1154.0, abs=5.0)
    assert skew["min"] <= skew["median"]
    assert skew["min"] == pytest.approx(-1182.6, abs=1.0)
    # one device, no collectives
    dev = reduced["devices"]["/device:TPU:0"]
    assert dev["comm_s"] == dev["comm_exposed_s"] == 0.0


def test_decode_ms_reads_the_probe_modules(reduced):
    """``decode_ms`` reads a module's device time over its runs; the probe's
    program stands in for the decode step under its name."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "decode_ms", HERE.parent / "metrics" / "decode_ms.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    class Win:
        trace = reduced
    assert mod.read({"window": Win}) is None
    Win.trace = dict(reduced, modules={
        "jit_decode_step": reduced["modules"]["jit__lambda"]})
    assert mod.read({"window": Win}) == pytest.approx(90.13e-3, rel=1e-3)
