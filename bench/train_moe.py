"""The MoE training cells: ``bench/train.py``'s run, with the MoE layer's
counters and the device time of its scopes.

``run`` and ``roofline_s`` are the runner that ``bench/run.py`` finds for a
traffic file of ``"kind": "train_moe"``.  The job is ``train.py``'s, built
from its parts (``Stream``, ``seed_weights``, ``Probe``, ``Hooks``,
``reference_steps``, ``compare``): one ``Session.train`` call, warm-up,
a window of ``--seconds``, then the plain reference follows the first
``check_steps`` steps.  Added here:

  counters  the train step's ``moe_assigned`` (assignments to held experts,
            summed over the MoE layers) and ``moe_max_load`` (the most on
            one held expert), read from each window step's metrics where
            the loss is read: their sum and largest over the window;
  scopes    with ``--trace 1``, the device seconds of the ops under each
            named scope (``bench/scopes.py``).  The compiled step's
            optimized HLO names each op's scope; it is read once, after the
            first step, by compiling the same call again (a hit in the
            persistent compile cache), before the window opens;
  warm-up   the first ``check_steps`` steps are each waited for before the
            next is dispatched (``FirstSteps``).

``roofline_s`` counts the window's flops with the reference's ``flops``,
the held experts' work from ``moe_assigned``, and also records the held
experts' own least time at the peak (``experts_roofline_s``), which
``expert_mfu`` reads.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import json
import os
import time

import jax
import numpy as np

import common
import scopes
import trace_reduce
from common import log
from train import (Hooks, Probe, Stream, WindowClosed, compare,
                   reference_steps, seed_weights)


class FirstSteps:
    """Stands in for the train step's function; every call runs as it is.
    The first ``n`` calls are waited for, so that what ``Probe`` draws
    after them (a weight leaf at a time) is never placed beside a running
    step's temporaries; with ``hlo``, the optimized HLO text of the same
    call is read after the first (``text``)."""

    def __init__(self, step, n: int, hlo: bool):
        self.fn, self.n, self.hlo, self.calls = step.fn, n, hlo, 0
        self.text = ""
        step.fn = self

    def __call__(self, params, opt, batch):
        if self.calls >= self.n:
            return self.fn(params, opt, batch)
        self.calls += 1
        args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding), (params, opt, batch))
        out = jax.block_until_ready(self.fn(params, opt, batch))
        if self.hlo and not self.text:
            self.text = self.fn.lower(*args).compile().as_text()
        return out


@contextlib.contextmanager
def scoped_traces(op_scopes):
    """While open, each window's trace reduction (``trace_reduce``, which
    ``common.compile_free_window`` calls) also gives ``scopes``: device
    seconds by named scope, the ops' scopes from ``op_scopes()``."""
    real = trace_reduce.reduce_trace

    def reduce(trace_dir):
        out = real(trace_dir)
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        out["scopes"] = scopes.scope_seconds(ProfileData.from_file(path),
                                             op_scopes())
        return out
    trace_reduce.reduce_trace = reduce
    try:
        yield
    finally:
        trace_reduce.reduce_trace = real


def roofline_s(cell, rec: dict, peak: dict) -> float:
    """Least device seconds the window's steps need at the chip's bf16
    peak; ``rec["experts_roofline_s"]``, the same of the held experts'
    grouped matmuls alone."""
    t, m = cell.traffic, cell.dims
    flop_s = peak["bf16_flops_per_s"] * cell.chips
    rec["experts_roofline_s"] = cell.reference.expert_flops(
        m, rec["moe_assigned"]) / flop_s
    return cell.reference.flops(m, rec["steps"] * int(t["batch"]),
                                int(t["seq"]), rec["moe_assigned"]) / flop_s


def run(cell, args, clock) -> tuple:
    """One training run: the records the metric readers take, and the
    numbers the cell's checks file compares."""
    from repro.core.steps import Strategy
    from repro.optim.optimizers import OptConfig
    t, m = cell.traffic, cell.dims
    B, S = int(t["batch"]), int(t["seq"])
    warmup, n_check = int(t["warmup_steps"]), int(t["check_steps"])
    opt = t["optimizer"]
    stream = Stream(m["V"], B, S, cell.seed, **t["stream"])
    strategy = Strategy(name=t["strategy"], opt=OptConfig(kind="adamw", **opt))
    hooks = Hooks(warmup, args.seconds, bool(args.trace), clock)
    session = cell.plan(batch=B, seq=S, strategy=strategy).compile()
    try:
        if "weights" in cell.config:
            seed_weights(cell, session.train_step)
        first = FirstSteps(session.train_step, n_check, bool(args.trace))
        probe = Probe(cell, session.train_step, n_check, opt["b1"])
        with scoped_traces(lambda: scopes.op_scopes(first.text)):
            try:
                session.train(stream, steps=warmup + 10 ** 6, hooks=hooks,
                              ckpt_dir="", log_every=10 ** 9, verbose=False)
            except WindowClosed:
                pass
            finally:
                hooks.close()
    finally:
        session.close()
    win = hooks.window
    peaks = common.memory_peaks(cell.chips)
    host = jax.device_get(hooks.metrics)
    losses = [float(x["loss"]) for x in host]
    prog = {"losses": losses[:n_check],
            "grad_norm": float(host[0]["grad_norm"]),
            "grad1": probe.grad1, "change": probe.change}
    del session, probe, hooks, first
    gc.collect()
    log(f"live_bytes[after the program]: {common.live_bytes()}")
    window = host[warmup:]
    window_losses = losses[warmup:]
    nonfinite = sum(not np.isfinite(x) for x in losses)
    steps = len(window_losses)
    assigned = sum(int(x["moe_assigned"]) for x in window)
    max_load = max((int(x["moe_max_load"]) for x in window), default=0)
    mean_load = assigned / max(steps * (m["L"] - m["L_dense"]) * m["E_held"],
                               1)
    log(f"window: {win.seconds:.3f} s, {steps} steps of {B} x {S} tokens "
        f"({steps * B * S / win.seconds:.1f} tok/s), compiles in window "
        f"{win.compiles}; memory peak per chip {peaks}; moe_assigned "
        f"{assigned}, moe_max_load / mean load {max_load} / "
        f"{mean_load:.1f} = {max_load / max(mean_load, 1e-9):.3f}")
    log(f"losses: warm-up {json.dumps(losses[:warmup])}, window first "
        f"{window_losses[:1]} last {window_losses[-1:]}; the last below "
        f"step 1's: {bool(window_losses and window_losses[-1] < losses[0])}")
    if win.trace and win.trace["devices"]:
        mods = win.trace["modules"].get("jit_train_step")
        log(f"trace: jit_train_step "
            f"{None if not mods else mods['seconds'] / mods['runs'] * 1e3} "
            f"ms a run over {None if not mods else mods['runs']} runs; "
            f"device s by scope {json.dumps(win.trace.get('scopes'))}")
    t0 = time.perf_counter()
    ref = reference_steps(cell, stream)
    readings = compare(prog, ref)
    log(f"reference: {n_check} steps in {time.perf_counter() - t0:.1f} s; "
        f"losses {ref['losses']} (program {prog['losses']}); grad_norm "
        f"{ref['grad_norm']} (program {prog['grad_norm']}); program "
        f"{json.dumps(readings)}")
    readings["nonfinite_steps"] = nonfinite
    program = readings
    if args.control:
        ctl = reference_steps(cell, stream, quant="fp8")
        readings = dict(compare(ctl, ref), nonfinite_steps=sum(
            not np.isfinite(x) for x in ctl["losses"]))
        log(f"control: the fp8 reference in the program's place: losses "
            f"{ctl['losses']}, grad_norm {ctl['grad_norm']}; "
            f"{json.dumps(readings)}")
    rec = {"window": win, "steps": steps, "tokens_per_step": B * S,
           "attempted": steps,
           "failed": sum(not np.isfinite(x) for x in window_losses),
           "memory_peak_bytes": max(peaks), "readings": program,
           "moe_assigned": assigned, "moe_max_load": max_load}
    return rec, readings
