"""Smoke check that the main path runs on a TPU: ``python chip_smoke.py``.

Everything runs in this one process, through ``Plan`` -> ``Session`` as a
user would call it, with random weights made from seed 0:

  serve   ``Session.serve_stream`` (the gateway) on qwen3-4b at its
          published widths and all 36 layers: 8 requests, 4 slots,
          128-token prompts, 32 generated tokens each.
  train   ``Session.train`` for 5 steps on qwen3-4b at full width, cut to
          4 layers and a 32,768-token vocabulary slice (with AdamW, the
          published 36 layers and 151,936-token vocabulary do not fit one
          chip's 16 GB), batch 4 x 1024 tokens, strategy phylanx.
  logits  one prefill's logits at the trained weights, computed by the
          same jitted function on the chip and on the host CPU.

``python chip_smoke.py --chips 4`` runs only the four-chip phase:
``Session.train`` on a data=2 x model=2 mesh against data=1 x model=1, same
seed and global batch, comparing per-step losses and printing each
device's peak memory.

The script exits non-zero, and prints no result line, when JAX finds no
TPU or when any phase or check fails.  The lines before the last describe
the device, the cuts, compile seconds and counters; none is a speed
number.  The last line of stdout is the result, for example
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite",
"count": 1}}``.

The JAX compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, and
otherwise to ``.jax_cache/`` in this checkout, so a second run compiles
less.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.frontend import Plan  # noqa: E402
from repro.launch.mesh import use_compile_cache  # noqa: E402
from repro.models.model import build_model  # noqa: E402

ARCH = "qwen3-4b"
SEED = 0
# the gateway phase: published widths and depth
SERVE = dict(plan=dict(arch=ARCH, tiny=False, seed=SEED),
             requests=8, slots=4, prompt_len=128, gen_len=32)
# the training phase: full width; 4 layers and a vocabulary slice
TRAIN = dict(plan=dict(arch=ARCH, tiny=False, seed=SEED, batch=4, seq=1024,
                       strategy="phylanx",
                       overrides={"n_layers": 4, "vocab": 32768}),
             steps=5)
# why each cut of TRAIN was made (compile of the train step for v5e)
CUT_REASON = ("with AdamW state, 2 layers at the full 151,936-token "
              "vocabulary already need 11.76 GB of arguments + 4.25 GB of "
              "temporaries on a 16 GB chip")
# prefill logits, chip vs host CPU, both in bf16 compute: relative L2
# error.  One bf16 rounding is 2^-9 (~0.2%); 4 layers and the unembed
# compound a few of them, so 2% leaves headroom while a wrong kernel,
# layout or sharding gives errors of order 1.
LOGITS_REL_TOL = 2e-2
# four-chip phase: largest |loss(2x2) - loss(1x1)| over the steps.  The
# two runs differ only in reduction order (bf16 matmuls, the data-axis
# mean of gradients), so losses near ln(32768) ~ 10.4 agree to ~1e-3.
LOSS_ABS_TOL = 2e-2


def check(ok: bool, what: str):
    print(f"check: {'pass' if ok else 'FAIL'}: {what}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def require_tpu(chips: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{d.platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX sees {len(devs)}")
    print(f"device: platform={d.platform} kind={d.device_kind!r} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class CompileClock:
    """Seconds JAX spends in backend compiles (and persistent-cache hits),
    summed from ``jax.monitoring`` events: set-up time, not speed."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, name: str, since: tuple):
        print(f"compile_s[{name}]: {self.seconds - since[0]:.1f} "
              f"(persistent-cache hits {self.cache_hits - since[1]})",
              flush=True)

    def mark(self) -> tuple:
        return (self.seconds, self.cache_hits)


def print_cuts(name: str, plan):
    published = get_config(plan.arch)
    used = plan.config()
    cuts = [f"{f.name} {getattr(published, f.name)} -> "
            f"{getattr(used, f.name)}"
            for f in dataclasses.fields(published)
            if getattr(published, f.name) != getattr(used, f.name)]
    print(f"cuts[{name}]: {plan.arch} " + ("; ".join(cuts) or
                                          "none (published config)"),
          flush=True)


def live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def serve_phase(clock: CompileClock):
    plan = Plan(**SERVE["plan"])
    print_cuts("serve", plan)
    print("note[serve]: the serve steps cast parameters to bf16", flush=True)
    mark = clock.mark()
    with plan.compile() as session:
        out = session.serve_stream(
            requests=SERVE["requests"], slots=SERVE["slots"],
            prompt_len=SERVE["prompt_len"], gen_len=SERVE["gen_len"])
    clock.phase("serve", mark)
    serve = out["runtime_stats"]["serve"]
    counters = {k: out[k] for k in ("completed", "failed", "expired",
                                     "rejected", "cancelled")}
    counters.update({k: serve.get(k, 0) for k in
                     ("page_hits", "refills", "prefill_recompute")})
    print(f"counters[serve]: {json.dumps(counters)}", flush=True)
    vocab = plan.config().vocab
    toks = [t for s in out["streams"].values() for t in s]
    check(out["completed"] == SERVE["requests"],
          f"completed == requests ({out['completed']} of "
          f"{SERVE['requests']})")
    check(out["failed"] == out["expired"] == out["rejected"] == 0,
          "no request failed, expired or was rejected")
    check(counters["page_hits"] == counters["refills"]
          and counters["prefill_recompute"] == 0,
          "every refill hit the page cache; prefill never recomputed")
    check(all(len(s) == SERVE["gen_len"] + 1
              for s in out["streams"].values()),
          f"every stream holds 1 prefill + {SERVE['gen_len']} decoded tokens")
    check(all(0 <= t < vocab for t in toks),
          f"every token id lies in [0, {vocab})")


def train(plan, clock: CompileClock, name: str) -> dict:
    mark = clock.mark()
    with plan.compile() as session:
        out = session.train(steps=TRAIN["steps"], log_every=1, ckpt_dir="")
    clock.phase(name, mark)
    return out


def train_phase(clock: CompileClock):
    plan = Plan(**TRAIN["plan"])
    print_cuts("train", plan)
    print(f"cut-reason[train]: {CUT_REASON}", flush=True)
    out = train(plan, clock, "train")
    losses = out["losses"]
    print(f"losses[train]: {json.dumps(losses)}", flush=True)
    check(len(losses) == TRAIN["steps"]
          and all(math.isfinite(x) for x in losses),
          f"{TRAIN['steps']} finite training losses")
    return plan, out["params"]


def logits_phase(plan, params, clock: CompileClock):
    """The same jitted prefill on the chip and on the host CPU."""
    model = build_model(plan.config())
    n = SERVE["prompt_len"]
    rng = np.random.default_rng(SEED)
    tokens = rng.integers(0, plan.config().vocab, (1, n)).astype(np.int32)
    fn = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, n)[0])
    mark = clock.mark()
    chip = np.asarray(fn(params, jnp.asarray(tokens)), np.float32)
    cpu = jax.devices("cpu")[0]
    host = np.asarray(fn(jax.device_put(params, cpu),
                         jax.device_put(tokens, cpu)), np.float32)
    clock.phase("logits", mark)
    rel = float(np.linalg.norm(chip - host) / np.linalg.norm(host))
    print(f"logits[chip vs cpu]: shape={list(chip.shape)} rel_l2={rel:.3e} "
          f"max_abs={float(np.abs(chip - host).max()):.3e} "
          f"argmax_equal={bool(chip.argmax() == host.argmax())}",
          flush=True)
    check(bool(np.isfinite(chip).all()) and chip.shape == host.shape,
          "chip logits are finite and of the host's shape")
    check(rel <= LOGITS_REL_TOL,
          f"chip and host logits agree (relative L2 {rel:.3e} <= "
          f"{LOGITS_REL_TOL})")


def peaks() -> list:
    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def param_bytes_per_device(params) -> dict:
    per: dict = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return per


def four_chip_phase(clock: CompileClock):
    """data=2 x model=2 against data=1 x model=1, in that order: peak
    memory only grows, so the mesh run's per-device peaks come first."""
    mesh_plan = Plan(**dict(TRAIN["plan"], data=2, model=2))
    print_cuts("train", mesh_plan)
    print(f"cut-reason[train]: {CUT_REASON}", flush=True)
    mesh_out = train(mesh_plan, clock, "train 2x2")
    per = param_bytes_per_device(mesh_out["params"])
    total = sum(x.nbytes for x in jax.tree.leaves(mesh_out["params"]))
    mesh_losses = mesh_out["losses"]
    del mesh_out
    gc.collect()
    print(f"peak_bytes_in_use[after 2x2]: {peaks()}", flush=True)
    print(f"param_bytes_per_device[2x2]: {json.dumps(per)} "
          f"(whole model {total})", flush=True)
    one_out = train(Plan(**dict(TRAIN["plan"], data=1, model=1)), clock,
                    "train 1x1")
    one_losses = one_out["losses"]
    del one_out
    gc.collect()
    print(f"peak_bytes_in_use[after 1x1]: {peaks()}", flush=True)
    print(f"losses[2x2]: {json.dumps(mesh_losses)}", flush=True)
    print(f"losses[1x1]: {json.dumps(one_losses)}", flush=True)
    diff = max(abs(a - b) for a, b in zip(mesh_losses, one_losses))
    check(len(mesh_losses) == len(one_losses) == TRAIN["steps"]
          and all(math.isfinite(x) for x in mesh_losses + one_losses),
          f"{TRAIN['steps']} finite losses in both runs")
    check(diff <= LOSS_ABS_TOL,
          f"2x2 and 1x1 losses agree (max |diff| {diff:.3e} <= "
          f"{LOSS_ABS_TOL})")
    check(len(per) == 4 and all(b > 0 for b in per.values()),
          "the 2x2 parameters span all 4 devices")
    check(max(per.values()) <= 0.75 * total,
          "no device holds the whole model (model-axis sharding is real)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the data=2 x model=2 training phase")
    args = ap.parse_args(argv)
    device = require_tpu(args.chips)
    print(f"compile-cache: {use_compile_cache()}", flush=True)
    clock = CompileClock()
    if args.chips == 4:
        four_chip_phase(clock)
    else:
        serve_phase(clock)
        gc.collect()
        print(f"live_bytes[after serve]: {live_bytes()}", flush=True)
        plan, params = train_phase(clock)
        logits_phase(plan, params, clock)
    print(f"compile_s[total]: {clock.seconds:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
