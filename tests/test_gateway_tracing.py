"""The serving path's spans and counters (runtime, gateway, paging) and the
names of the jitted steps, on the CPU at tiny size.

One live-queue ``serve_stream`` run is traced with ``jax.profiler``: the
gateway starts idle, three requests arrive for two slots (so one joins a
running batch), and the queue closes once all are done.  Its host spans
and its ``stats().serve`` counters are then read back."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs import get_config
from repro.core import steps as steps_lib
from repro.core.sharding import param_structs
from repro.frontend import Plan
from repro.frontend.gateway import RequestQueue

ARCH = "qwen2.5-3b"
REQUESTS, PROMPT, GEN, SLOTS = 3, 16, 6, 2

SPANS = {"node.stack", "node.prefill", "node.refill", "node.decode",
         "node.emit", "node.finish", "gateway.round",
         "gateway.force_prefill", "gateway.lookahead_wait",
         "gateway.idle_wait", "gateway.cache_to_host", "gateway.scatter",
         "paging.put", "paging.get"}


def _feed(queue, prompts, box):
    try:
        handles = [queue.submit(p) for p in prompts]
        for h in handles:
            h.result(timeout=300)
        box["handles"] = handles
    finally:
        queue.close()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(host events as (name, stats) pairs, serve_stream's result, the
    decode step)."""
    tdir = str(tmp_path_factory.mktemp("trace"))
    rng = np.random.default_rng(0)
    with Plan(arch=ARCH).compile() as session:
        prompts = rng.integers(0, session.cfg.vocab,
                               (REQUESTS, PROMPT)).astype(np.int32)
        queue, box = RequestQueue(), {}
        feeder = threading.Thread(target=_feed, args=(queue, prompts, box),
                                  daemon=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        with jax.profiler.trace(tdir, profiler_options=opts):
            feeder.start()
            out = session.serve_stream(queue=queue, prompt_len=PROMPT,
                                       gen_len=GEN, slots=SLOTS,
                                       verbose=False)
        feeder.join(timeout=60)
        dec = session._gateway.dec
    assert not feeder.is_alive()
    assert len(box["handles"]) == REQUESTS
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    pd = ProfileData.from_file(files[0])
    events = [(e.name, dict(e.stats)) for p in pd.planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events]
    return events, out, dec


def test_every_span_is_recorded(traced):
    events, out, _ = traced
    assert out["completed"] == REQUESTS
    names = {n for n, _ in events}
    assert SPANS <= names, sorted(SPANS - names)


@pytest.mark.parametrize("span", ["node.prefill", "paging.put",
                                  "paging.get"])
def test_request_spans_carry_the_rid(traced, span):
    """The paging spans carry ``rid``; a node span carries its node's
    whole name, which for a request's node ends in its rid."""
    events, out, _ = traced
    rids = {st["rid"] if "rid" in st else st["name"].split(":", 1)[1]
            for n, st in events if n == span}
    assert rids == {h.rid for h in out["handles"]}


def test_decode_round_spans_carry_replica_epoch_and_round(traced):
    events, _, _ = traced
    waits = [st for n, st in events if n == "gateway.lookahead_wait"]
    assert waits and all({"replica", "epoch", "j"} <= set(st)
                         for st in waits)
    decodes = [st["name"] for n, st in events if n == "node.decode"]
    assert decodes and all(d.startswith("decode:e") for d in decodes)


def test_counters_after_every_request_finished(traced):
    _, out, dec = traced
    serve = out["runtime_stats"]["serve"]
    # the decode state of one request: every cache leaf at batch 1
    state = sum(int(np.prod([1 if d == "batch" else n for n, d in
                             zip(sp.shape, sp.dims)]))
                * jnp.dtype(sp.dtype).itemsize
                for sp in jax.tree.leaves(
                    dec.cache_specs, is_leaf=lambda x: hasattr(x, "dims")))
    assert state > 0
    assert serve["refills"] == REQUESTS
    assert serve["d2h_bytes"] == REQUESTS * state
    assert serve["h2d_bytes"] == REQUESTS * state
    for k in ("join_wait_us", "refill_us", "page_put_us", "page_get_us"):
        assert serve[k] > 0, k


@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_jitted_steps_are_named_by_role(kind):
    cfg = get_config(ARCH, tiny=True)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    shape = {"seq_len": 16, "global_batch": 2, "kind": kind}
    step = steps_lib.make_step(cfg, mesh, steps_lib.Strategy(), shape)
    scfg = steps_lib._serve_cfg(cfg)
    if kind == "train":
        args = (step.param_structs(), step.opt_structs(),
                steps_lib.input_specs(cfg, shape))
    elif kind == "prefill":
        args = (param_structs(step.specs), steps_lib.input_specs(scfg, shape))
    else:
        args = (param_structs(step.specs), param_structs(step.cache_specs),
                steps_lib.input_specs(scfg, shape),
                jax.ShapeDtypeStruct((2,), jnp.int32))
    text = step.fn.lower(*args).as_text()
    assert f"module @jit_{kind}_step" in text
