"""Frontend: @futurize tracing, Plan/Session, shared CLI flags, and parity
with the launcher shims (resume-from-checkpoint drill)."""
import os
import time

import numpy as np
import pytest

from repro.core import steps as steps_lib
from repro.core.futures import FuturizedGraph, Lane, PhyFuture
from repro.frontend import (Plan, cli_args, futurize, plan_from_args,
                            tracing)

ARCH = "qwen2.5-3b"


def _plan(**kw):
    kw.setdefault("arch", ARCH)
    kw.setdefault("batch", 4)
    kw.setdefault("seq", 16)
    return Plan(**kw)


# -- @futurize tracing -------------------------------------------------------

def test_untraced_futurized_call_runs_inline():
    @futurize
    def f(x):
        return x + 1
    assert f(1) == 2                      # plain value, no graph involved


def test_traced_calls_become_graph_nodes_with_edges():
    @futurize
    def load(i):
        return i * 10

    @futurize
    def use(x):
        return x + 1

    with tracing() as tr:
        a = load(3)
        b = use(a)
        assert isinstance(a, PhyFuture) and isinstance(b, PhyFuture)
        assert b.result() == 31
    sig = tr.signature()
    assert sig[0] == ("load:0", "COMPUTE", ())
    assert sig[1] == ("use:0", "COMPUTE", (0,))   # edge found from the arg


def test_traced_tree_shape_is_deterministic_across_runs():
    def program():
        @futurize
        def load(i):
            return i

        @futurize
        def mul(x, y):
            return x * y

        with tracing() as tr:
            xs = [load(i) for i in range(4)]
            ys = [mul(xs[i], xs[(i + 1) % 4]) for i in range(4)]
            assert tr.graph.when_all(ys).result() == [0, 2, 6, 0]
        return tr.signature()

    assert program() == program()


def test_futurize_composes_with_when_all_and_tree_join():
    @futurize
    def val(i):
        return i

    with tracing() as tr:
        g = tr.graph
        futs = [val(i) for i in range(5)]
        assert g.when_all(futs).result() == [0, 1, 2, 3, 4]
        tree = {"a": futs[2], "b": [futs[4], 7]}
        assert g.tree_join(tree).result() == {"a": 2, "b": [4, 7]}


def test_nested_futurized_calls_run_inline_on_workers():
    @futurize
    def inner(x):
        return x * 2

    @futurize
    def outer(x):
        return inner(x) + 1     # runs on a worker thread: inline fallback

    with tracing() as tr:
        assert outer(5).result() == 11
    assert [n.name for n in tr.nodes] == ["outer:0"]


def test_futurize_lane_and_untrace_on_exit():
    @futurize(lane=Lane.PREFETCH, name="fetch")
    def f():
        return 1

    with tracing() as tr:
        fut = f()
        assert fut.lane is Lane.PREFETCH
        fut.result()
    assert f() == 1                       # context exited: inline again
    assert tr.nodes[0].name == "fetch:0"


# -- runtime stats histograms ------------------------------------------------

def test_runtime_stats_histograms_bucketed_by_lane():
    g = FuturizedGraph(max_workers=2, name="hist")
    try:
        for _ in range(4):
            g.defer(time.sleep, 0.002, lane=Lane.PREFETCH).result()
        g.defer(lambda: None, lane=Lane.CHECKPOINT).result()
    finally:
        g.shutdown(wait=True)
    js = g.stats().to_json()
    hist = js["lane_time_hist"]
    assert hist["edges_s"] == [1e-4, 1e-3, 1e-2, 1e-1, 1.0]
    assert sum(hist["counts"]["PREFETCH"]) == 4
    assert sum(hist["counts"]["CHECKPOINT"]) == 1
    # histogram totals agree with the per-lane completion counters
    for lane, counts in hist["counts"].items():
        assert sum(counts) == js["per_lane"][lane]
    assert g.stats().hist_lines()         # non-empty human-readable form


# -- Plan / Session ----------------------------------------------------------

def test_steps_builders_accept_plan_keyword():
    plan = _plan()
    step = steps_lib.make_train_step(plan=plan)
    assert isinstance(step, steps_lib.TrainStep)
    assert step.strategy.name == "phylanx"
    # explicit arguments win over the plan
    step2 = steps_lib.make_train_step(
        plan=plan, strategy=steps_lib.Strategy(name="horovod"))
    assert step2.strategy.name == "horovod"


def test_cli_args_shared_flags_and_plan_from_args():
    ap = cli_args(seq=64, batch=8)
    args = ap.parse_args(["--arch", ARCH, "--full", "--batch", "2"])
    assert args.tiny is False and args.batch == 2 and args.data == 1
    plan = plan_from_args(args, tiny=True)
    assert plan.arch == ARCH and plan.batch == 2 and plan.tiny is True


def test_session_train_resume_matches_launcher(tmp_path):
    """Session drill: train, 'crash', resume on the same session - and the
    result must equal an uninterrupted launcher-shim run bit-for-bit."""
    from repro.launch import train as train_mod

    hooks_seen = []

    class Hooks:
        def on_log(self, it, loss):
            hooks_seen.append((it, loss))

    with _plan().compile() as session:
        with pytest.raises(RuntimeError, match="injected node failure"):
            session.train(steps=8, ckpt_dir=str(tmp_path), ckpt_every=4,
                          log_every=4, fail_at_step=6, verbose=False)
        out = session.train(steps=8, ckpt_dir=str(tmp_path), ckpt_every=4,
                            log_every=4, resume=True, hooks=Hooks(),
                            verbose=False)
    assert np.isfinite(out["final_loss"])
    assert hooks_seen and hooks_seen[-1][0] == 7

    args = train_mod.parser().parse_args(
        ["--arch", ARCH, "--steps", "8", "--batch", "4", "--seq", "16",
         "--log-every", "4"])
    ref = train_mod.run(args)
    assert abs(ref["final_loss"] - out["final_loss"]) < 1e-4


def test_session_serve_decode_steps_are_named_graph_nodes():
    with _plan().compile() as session:
        out = session.serve(requests=4, slots=2, prompt_len=16, gen_len=4,
                            verbose=False)
    assert out["tokens_per_s"] > 0
    decode = [n for n in out["nodes"] if n.startswith("decode:")]
    assert decode == [f"decode:w{w}:t{t}" for w in range(2)
                      for t in range(4)]
    # decode rides the step-critical COMPUTE lane; wave prep is PREFETCH
    trace = {name: lane for name, lane, _ in out["trace"]}
    assert trace["decode:w0:t0"] == "COMPUTE"
    assert trace["wave:0"] == "PREFETCH"
    # each decode node's edge is the previous node in its wave's chain
    by_name = {name: deps for name, _, deps in out["trace"]}
    idx = {name: i for i, (name, _, _) in enumerate(out["trace"])}
    assert by_name["decode:w0:t1"] == (idx["decode:w0:t0"],)


def test_session_serve_zero_requests_serves_nothing():
    with _plan().compile() as session:
        out = session.serve(requests=0, slots=2, prompt_len=16, gen_len=4,
                            verbose=False)
    assert out["requests"] == 0 and out["tokens"] == 0
    assert out["tokens_per_s"] == 0.0 and out["nodes"] == []


def test_entry_points_place_the_compile_cache_from_outside(monkeypatch):
    import jax

    from repro.launch.mesh import use_compile_cache
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/the/caller")
    assert use_compile_cache() == "/set/by/the/caller"
    assert jax.config.jax_compilation_cache_dir == old    # JAX reads it
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        checkout = os.path.dirname(path)
        assert os.path.basename(path) == ".jax_cache"
        assert os.path.isdir(os.path.join(checkout, "src", "repro"))
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
