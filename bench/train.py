"""The training cells: ``Session.train`` driven through its own hooks.

``run`` and ``roofline_s`` are the runner that ``bench/run.py`` finds for a
traffic file of ``"kind": "train"``.  One ``Session.train`` call runs the
whole job, on the mesh, batch, sequence length, strategy and optimizer the
traffic file gives, fed by ``Stream`` through the session's prefetch nodes:

  weights   drawn by the program, or, where the configuration names a
            ``"weights"`` rule, drawn by the reference from the seed and
            handed to the program (``seed_weights``);
  warm-up   ``warmup_steps`` steps.  The first compiles the step; the first
            ``check_steps`` are the ones the reference follows.  Their state
            is read where the program leaves it: the gradient of step 1 as
            the optimizer got it (its first moment over ``1 - b1``), and the
            change of every weight after step ``check_steps`` (the weights
            against those drawn from the seed);
  window    opens at a step boundary once the warm-up's last step is done,
            and closes at the first step boundary ``seconds`` later, after
            blocking on that step's loss.  A step counts when it was
            dispatched inside the window;
  check     the program freed, the plain reference (float32, ``highest``)
            runs the same ``check_steps`` steps from the same weights on the
            same batches, and the numbers of ``readings`` compare the two.

``--control 1`` puts the reference computed in fp8 in the program's place.
"""
from __future__ import annotations

import gc
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

import common
import flops
from common import compile_free_window, log


class Stream:
    """Rows of a noisy affine bigram process from (seed, step): each row
    starts at a uniform token; then ``x[t+1] = (a * x[t] + b) mod vocab``,
    or with probability ``noise`` a uniform token.  Every seed gives the
    same sizes; the rows differ within and across steps."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int,
                 noise: float, a: int, b: int):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed
        self.noise, self.a, self.b = noise, a, b

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng([self.seed, step])
        x = np.empty((self.batch, self.seq + 1), np.int64)
        x[:, 0] = rng.integers(0, self.vocab, self.batch)
        flip = rng.random((self.batch, self.seq)) < self.noise
        noise = rng.integers(0, self.vocab, (self.batch, self.seq))
        for t in range(self.seq):
            x[:, t + 1] = np.where(flip[:, t], noise[:, t],
                                   (self.a * x[:, t] + self.b) % self.vocab)
        x = x.astype(np.int32)
        return {"tokens": x[:, :-1], "labels": x[:, 1:]}


class WindowClosed(Exception):
    """Raised from the step hook to end ``Session.train`` at the close."""


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))), tree)


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32) - b)))


def flat(tree) -> dict:
    """A nested dict of leaves keyed by path tuples."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, v in leaves:
        key = tuple(k.key for k in path)
        # a flat dict already keyed by path tuples
        out[key[0] if len(key) == 1 and isinstance(key[0], tuple)
            else key] = v
    return out


def norms(tree, scale: float = 1.0) -> dict:
    """Each leaf's L2 norm, times ``scale``, keyed by path."""
    return {k: float(v) * scale
            for k, v in flat(jax.device_get(_leaf_norms(tree))).items()}


def change_norms(cell, weights: dict) -> dict:
    """Each leaf's distance from the weights drawn from the seed, the
    drawn leaf placed as the leaf is and freed before the next."""
    out = {}
    for path, w0 in cell.reference.iter_weights(
            cell.config, cell.program_seed, jnp.float32,
            lambda path, shape: weights[path].sharding):
        out[path] = float(_diff_norm(weights[path], w0))
        del w0
    return out


def seed_weights(cell, step):
    """Has the train step's ``init`` return the weights that the reference
    draws from the seed, each placed as the program places that leaf, in
    place of the program's own draw (the optimizer state is the program's;
    it does not depend on the weights' values).  Used where the
    configuration names its own rule (``"weights"``)."""
    real = step.init

    def init(key):
        params, opt = real(key)
        leaves = flat(params)
        treedef = jax.tree.structure(params)
        place = {k: v.sharding for k, v in leaves.items()}
        dtype, = {v.dtype for v in leaves.values()}
        del params, leaves
        w = cell.reference.make_weights(cell.config, cell.program_seed, dtype,
                                        lambda path, shape: place[path])
        return jax.tree.unflatten(treedef, [w[k] for k in place]), opt
    step.init = init


class Probe:
    """Stands in for the train step's function for the first
    ``check_steps`` calls: the step runs as it is, and its outputs are read
    before the next step takes them over."""

    def __init__(self, cell, step, check_steps: int, b1: float):
        self.cell, self.step, self.fn = cell, step, step.fn
        self.n, self.b1, self.calls = check_steps, b1, 0
        self.grad1: dict = {}
        self.change: dict = {}
        step.fn = self

    def __call__(self, params, opt, batch):
        out = self.fn(params, opt, batch)
        self.calls += 1
        _, params, opt = out
        if self.calls == 1:
            self.grad1 = norms(opt["m"], 1.0 / (1.0 - self.b1))
        if self.calls == self.n:
            self.change = change_norms(self.cell, flat(params))
            self.step.fn = self.fn
        return out


class Hooks:
    """``Session.train``'s hooks: keep every step's metrics, open the
    window when the warm-up's last step is done and close it at the first
    step dispatched ``seconds`` after."""

    def __init__(self, warmup: int, seconds: float, trace: bool, clock):
        self.warmup, self.seconds = warmup, seconds
        self.trace, self.clock = trace, clock
        self.metrics: list = []
        self.window: common.Window | None = None
        self._cm = None

    def on_step(self, it, metrics):
        self.metrics.append(metrics)
        if it == self.warmup - 1:
            jax.block_until_ready(metrics)
            self._cm = compile_free_window(self.clock, self.trace)
            self.window = self._cm.__enter__()
        elif it >= self.warmup and \
                time.perf_counter() - self.window.t_open >= self.seconds:
            jax.block_until_ready(metrics["loss"])
            self.close()
            raise WindowClosed

    def close(self):
        if self._cm is not None:
            cm, self._cm = self._cm, None
            cm.__exit__(None, None, None)


def roofline_s(cell, rec: dict, peak: dict) -> float:
    """Least device seconds the window's steps need at the chips' bf16
    peak."""
    t = cell.traffic
    f = flops.train(cell.dims, rec["steps"] * int(t["batch"]), int(t["seq"]))
    return f / (peak["bf16_flops_per_s"] * cell.chips)


def reference_steps(cell, stream: Stream, quant=None) -> dict:
    """The reference's ``check_steps`` steps from the seed's weights on
    ``stream``: each step's loss, step 1's global gradient norm and its
    clipped gradient's leaf norms, and each leaf's change after the last
    step.  Float32, spread over the cell's chips."""
    ref, t = cell.reference, cell.traffic
    place = ref.spread(jax.devices()[:cell.chips])
    w = ref.make_weights(cell.config, cell.program_seed, jnp.float32, place)
    state, losses = None, []
    for k in range(int(t["check_steps"])):
        loss, g = ref.loss_and_grad(cell.config, w, stream.batch_at(k),
                                    quant=quant, rows=int(t["check_rows"]))
        w, state, gn, clipped = ref.adamw(w, g, state, k + 1,
                                          t["optimizer"])
        losses.append(float(loss))
        if k == 0:
            grad1, grad_norm = norms(clipped), float(gn)
        del g, clipped
    del state
    change = change_norms(cell, w)
    del w
    return {"losses": losses, "grad_norm": grad_norm, "grad1": grad1,
            "change": change}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers read against the reference, each a relative gap.

      loss_rel_gap       each step's loss, the worst step;
      grad_leaf_gap      step 1's gradient as the optimizer got it, each
                         leaf's norm, the worst leaf, against the
                         reference's norm of that leaf or of the median
                         leaf, whichever is larger;
      change_leaf_gap    each leaf's change after the last step, the worst
                         leaf, measured as the gradient is.  A leaf whose
                         reference gradient is under a thousandth of the
                         median leaf's moves by rounding alone and is left
                         out;
      grad_norm_rel_gap  step 1's global norm before clipping.
    The cell's checks file names those compared."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                   ref["losses"]))
    g_med = statistics.median(ref["grad1"].values())
    moved = [k for k, v in ref["grad1"].items() if v >= 1e-3 * g_med]
    c_med = statistics.median(ref["change"][k] for k in moved)

    def gaps(p, r, keys, med):
        return [abs(p[k] - r[k]) / max(r[k], med) for k in keys]
    return {"loss_rel_gap": loss,
            "change_leaf_gap": max(gaps(prog["change"], ref["change"], moved,
                                        c_med)),
            "grad_leaf_gap": max(gaps(prog["grad1"], ref["grad1"],
                                      list(ref["grad1"]), g_med)),
            "grad_norm_rel_gap": abs(prog["grad_norm"] - ref["grad_norm"])
            / ref["grad_norm"]}


def run(cell, args, clock) -> tuple:
    """One training run: the records the metric readers take, and the
    numbers the cell's checks file compares."""
    from repro.core.steps import Strategy
    from repro.optim.optimizers import OptConfig
    t = cell.traffic
    B, S = int(t["batch"]), int(t["seq"])
    warmup, n_check = int(t["warmup_steps"]), int(t["check_steps"])
    opt = t["optimizer"]
    stream = Stream(cell.dims["V"], B, S, cell.seed, **t["stream"])
    strategy = Strategy(name=t["strategy"], opt=OptConfig(kind="adamw", **opt))
    hooks = Hooks(warmup, args.seconds, bool(args.trace), clock)
    session = cell.plan(batch=B, seq=S, strategy=strategy).compile()
    try:
        if "weights" in cell.config:
            seed_weights(cell, session.train_step)
        probe = Probe(cell, session.train_step, n_check, opt["b1"])
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
    losses = [float(m["loss"]) for m in hooks.metrics]
    first = hooks.metrics[0]
    prog = {"losses": losses[:n_check], "grad_norm": float(first["grad_norm"]),
            "grad1": probe.grad1, "change": probe.change}
    del session, probe, hooks, first
    gc.collect()
    log(f"live_bytes[after the program]: {common.live_bytes()}")
    window_losses = losses[warmup:]
    nonfinite = sum(not np.isfinite(x) for x in losses)
    steps = len(window_losses)
    log(f"window: {win.seconds:.3f} s, {steps} steps of {B} x {S} tokens "
        f"({steps * B * S / win.seconds:.1f} tok/s), compiles in window "
        f"{win.compiles}; memory peak per chip {peaks}")
    log(f"losses: warm-up {json.dumps(losses[:warmup])}, window first "
        f"{window_losses[:1]} last {window_losses[-1:]}; the last below "
        f"step 1's: {bool(window_losses and window_losses[-1] < losses[0])}")
    if win.trace and win.trace["devices"]:
        mods = win.trace["modules"].get("jit_train_step")
        devs = win.trace["devices"].values()
        comm = statistics.mean(d["comm_s"] for d in devs)
        exposed = statistics.mean(d["comm_exposed_s"] for d in devs)
        log(f"trace: jit_train_step "
            f"{None if not mods else mods['seconds'] / mods['runs'] * 1e3} "
            f"ms a run over {None if not mods else mods['runs']} runs; "
            f"collectives {comm / max(steps, 1) * 1e3} ms a step, "
            f"exposed {exposed / comm if comm else None} of it")
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
           "memory_peak_bytes": max(peaks), "readings": program}
    return rec, readings
