"""Step builders: train / prefill / decode, with distribution wired in.

The train step is a ``jax.shard_map`` whose *manual* axes are the
data-parallel mesh axes ("pod","data") - so the gradient exchange and solver
are explicit framework code (core/overlap.py: horovod | phylanx | zero1) -
while the "model" axis stays *auto*: tensor/expert parallelism inside the
model is delegated to the SPMD partitioner driven by the tiling plans
(core/sharding.py).  This is DESIGN.md §2's mapping of Phylanx's
active-messaging collectives onto TPU-native constructs.

Serve steps (prefill/decode) are pure pjit programs; their KV-cache tiling
plan adapts per architecture (GQA heads sharded when divisible, otherwise
the cache's sequence dim goes on the model axis) and per shape (long-context
caches spread over "data" too).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import fusion, overlap
from .granularity import GrainPolicy
from .sharding import (ShardingRules, act_hook, default_rules, init_params,
                       param_shardings, param_structs, spec_for)
from ..models.model import build_model, reduce_stats
from ..optim.optimizers import OptConfig
from ..optim import optimizers as optim


# ---------------------------------------------------------------------------
# Strategy
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Strategy:
    name: str = "phylanx"            # phylanx | horovod | zero1 | onebit
    bucket_bytes: int = 0            # 0 -> runtime-adaptive (GrainPolicy)
    sequence_parallel: bool = False  # shard residual seq dim on "model"
    grad_accum: int = 1
    opt: OptConfig = dataclasses.field(default_factory=OptConfig)

    def resolve_bucket_bytes(self, cfg, mesh, n_tensors: int,
                             shape: dict) -> int:
        if self.bucket_bytes:
            return self.bucket_bytes
        tot, _ = cfg.n_params()
        dec = GrainPolicy.derive(
            n_params=tot, n_tensors=n_tensors,
            global_batch=shape.get("global_batch", 8),
            seq=shape.get("seq_len", 1024), d_model=cfg.d_model,
            n_layers=cfg.n_layers, head_dim=max(cfg.head_dim, 1),
            dp_degree=dp_degree(mesh))
        return dec.bucket_bytes


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def dp_degree(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def _serve_cfg(cfg):
    return dataclasses.replace(cfg, param_dtype="bf16", remat=False)


def _batch_spec(mesh, name: str) -> P:
    axes = dp_axes(mesh)
    return P(axes if len(axes) > 1 else (axes[0] if axes else None))


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs - never allocated; spec step 2)
# ---------------------------------------------------------------------------
def input_specs(cfg, shape: dict) -> dict:
    """Stand-ins for every model input of a (arch x shape) cell."""
    B, S = shape["global_batch"], shape["seq_len"]
    kind = shape["kind"]
    i32 = jnp.int32
    if kind == "train":
        out = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
               "labels": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_frames, cfg.d_model), cfg.c_dtype)
        return out
    if kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_frames, cfg.d_model), cfg.c_dtype)
        return out
    if kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    raise ValueError(kind)


def batch_shardings(cfg, mesh, shape: dict):
    spec = _batch_spec(mesh, "batch")
    sh = {}
    for k, v in input_specs(cfg, shape).items():
        # shard dim0 (batch) over dp axes when divisible
        n = dp_degree(mesh)
        use = spec if (v.shape and v.shape[0] % max(n, 1) == 0 and n > 1) else P()
        sh[k] = NamedSharding(mesh, use)
    return sh


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TrainStep:
    # buffers fn donates per call (donate_argnums=(0, 1) below); the
    # phylint step-contract builder lints against this declaration
    donated_buffers = ("params", "opt")
    fn: Any                      # jitted (params, opt, batch) -> (metrics, params, opt)
    fn_nodonate: Any = None      # for resilience replay/replicate (inputs kept)
    model: Any = None
    specs: Any = None            # ParamSpec tree
    param_shardings: Any = None
    opt_shardings: Any = None
    batch_shardings: Any = None
    rules: Any = None
    plan: Any = None
    strategy: Any = None
    mesh: Any = None
    scatter_mask: Any = None

    def _ndp(self):
        return dp_degree(self.mesh) if self.mesh is not None else 1

    def init(self, key):
        params = init_params(self.specs, key, self.param_shardings)

        def opt_state(params):
            if self.strategy.name == "zero1":
                return overlap.zero1_init_state(self.specs, self.scatter_mask,
                                                self._ndp())
            opt = optim.init(params, self.strategy.opt)
            if self.strategy.name == "onebit":
                from ..optim.compression import ROW
                ndp = self._ndp()
                opt["ef"] = [jnp.zeros((ndp * b.size // ROW, ROW), jnp.float32)
                             for b in self.plan.buckets]
            return opt
        # born sharded: no device holds a whole copy of the state
        opt = jax.jit(opt_state, out_shardings=self.opt_shardings)(params)
        return params, opt

    def param_structs(self):
        return param_structs(self.specs)

    def opt_structs(self):
        if self.strategy.name == "zero1":
            z = lambda: jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                param_structs(self.specs))
            return {"count": jax.ShapeDtypeStruct((), jnp.int32),
                    "m": z(), "v": z()}
        zeros = lambda: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
            param_structs(self.specs))
        out = {"count": jax.ShapeDtypeStruct((), jnp.int32)}
        if self.strategy.opt.kind == "adamw":
            out["m"], out["v"] = zeros(), zeros()
        elif self.strategy.opt.kind == "momentum":
            out["m"] = zeros()
        if self.strategy.name == "onebit":
            from ..optim.compression import ROW
            ndp = self._ndp()
            out["ef"] = [jax.ShapeDtypeStruct((ndp * b.size // ROW, ROW),
                                              jnp.float32)
                         for b in self.plan.buckets]
        return out


def make_train_step(cfg=None, mesh=None, strategy: Optional[Strategy] = None,
                    shape: Optional[dict] = None, *, plan=None) -> TrainStep:
    if plan is not None:
        cfg, mesh, strategy, shape = plan.resolve(
            "train", cfg=cfg, mesh=mesh, strategy=strategy, shape=shape)
    model = build_model(cfg)
    specs = model.specs()
    rules = default_rules(sequence_parallel=strategy.sequence_parallel)
    p_shard = param_shardings(specs, mesh, rules)
    # manual axes of size 1 make every dp collective a no-op; drop them so
    # the dp=1 case is a plain pjit program
    axes = tuple(a for a in dp_axes(mesh) if mesh.shape[a] > 1)
    ndp = dp_degree(mesh)
    structs = param_structs(specs)
    n_tensors = len(jax.tree.leaves(structs))
    bucket_bytes = strategy.resolve_bucket_bytes(cfg, mesh, n_tensors, shape)
    oc = strategy.opt

    plan = None
    f32_structs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), structs)
    scatter_mask = None
    if strategy.name == "zero1":
        scatter_mask = overlap.zero1_scatter_mask(specs, mesh, rules, ndp)
    elif strategy.name == "onebit":
        from ..optim import compression
        plan = compression.make_plan(f32_structs, ndp)

    # tensors safe to coalesce into fused buckets: not sharded on "model"
    # (flattening TP-sharded grads de-shards them; see overlap.py)
    def _fusable(sp):
        pspec = spec_for(mesh, rules, sp.shape, sp.dims)
        return not any("model" in ((p,) if isinstance(p, str) else tuple(p or ()))
                       for p in pspec)
    fuse_mask = jax.tree.map(_fusable, specs,
                             is_leaf=lambda x: hasattr(x, "dims"))

    def loss_fn(params, batch):
        # the MoE counters ride out of the forward beside the loss
        with model.recording() as stats:
            loss = model.loss(params, batch)
        return loss, {k: v for k, v in stats.items() if k.startswith("moe_")}

    def grads_of(params, batch):
        if strategy.grad_accum <= 1:
            return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        k = strategy.grad_accum
        micro = jax.tree.map(
            lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)

        def acc(carry, mb):
            (l, st), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            st = reduce_stats(jax.tree.map(lambda *v: jnp.stack(v),
                                           carry[0][1], st))
            return ((carry[0][0] + l / k, st),
                    jax.tree.map(lambda a, b: a + b / k, carry[1], g)), None
        zero_g = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                              structs)
        zero_st = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(loss_fn, params,
                           jax.tree.map(lambda x: x[0], micro))[1])
        (l, g), _ = jax.lax.scan(
            acc, ((jnp.zeros((), jnp.float32), zero_st), zero_g), micro)
        return l, g

    def train_step(params, opt_state, batch):
        # inside shard_map the batch dim is already local: constrain only
        # auto-axis (model) placements; seq joins under sequence parallelism
        with act_hook(mesh, rules.with_overrides(batch=None)):
            (loss, counts), grads = grads_of(params, batch)
        loss = jax.lax.pmean(loss, axes) if axes else loss
        if axes:
            counts = {k: (jax.lax.pmax if k == "moe_max_load"
                          else jax.lax.psum)(v, axes)
                      for k, v in counts.items()}
        if strategy.name == "zero1":
            params, opt_state, m = overlap.zero1_update(
                grads, opt_state, params, oc, axes, scatter_mask)
        elif strategy.name == "onebit" and axes:
            from ..optim import compression
            grads_r, new_ef = compression.exchange_onebit(
                grads, opt_state["ef"], axes, plan)
            inner = {k: v for k, v in opt_state.items() if k != "ef"}
            params, inner, m = optim.update(grads_r, inner, params, oc)
            opt_state = dict(inner, ef=new_ef)
        else:
            if axes:
                grads_r = (overlap.exchange_horovod(grads, axes)
                           if strategy.name == "horovod" else
                           overlap.exchange_phylanx(grads, axes, bucket_bytes,
                                                    fuse_mask=fuse_mask))
            else:
                grads_r = grads
            params, opt_state, m = optim.update(grads_r, opt_state, params, oc)
        metrics = {"loss": loss, "grad_norm": m["grad_norm"], **counts}
        return metrics, params, opt_state

    if axes:
        if strategy.name == "zero1":
            opt_specs = overlap.zero1_state_shard_specs(scatter_mask, axes)
        elif strategy.name == "onebit":
            opt_specs = _opt_skeleton(oc)
            opt_specs["ef"] = [P(tuple(axes)) for _ in plan.buckets]
        else:
            opt_specs = _opt_skeleton(oc)  # prefix tree of P()
        bspec = _batch_spec(mesh, "batch")
        fn = jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(P(), opt_specs, bspec),
            out_specs=(P(), P(), opt_specs),
            axis_names=set(axes), check_vma=False)
    else:
        fn = train_step

    # shardings for init/IO
    if strategy.name == "onebit":
        f32_specs = optim.init_specs(specs, oc)
        opt_sh = param_shardings(f32_specs, mesh, rules)
        dp_spec = P(axes if len(axes) > 1 else (axes[0] if axes else None))
        opt_sh["ef"] = [NamedSharding(mesh, dp_spec) for _ in plan.buckets]
    elif strategy.name == "zero1":
        def _state_sh(sp, sc):
            pspec = spec_for(mesh, rules, sp.shape, sp.dims)
            parts = list(pspec) + [None] * (len(sp.shape) - len(pspec))
            if sc and axes:
                parts[0] = axes if len(axes) > 1 else axes[0]
            return NamedSharding(mesh, P(*parts))
        per = jax.tree.map(_state_sh, specs, scatter_mask,
                           is_leaf=lambda x: hasattr(x, "dims"))
        opt_sh = {"count": NamedSharding(mesh, P()), "m": per,
                  "v": jax.tree.map(_state_sh, specs, scatter_mask,
                                    is_leaf=lambda x: hasattr(x, "dims"))}
    else:
        f32_specs = optim.init_specs(specs, oc)
        opt_sh = param_shardings(f32_specs, mesh, rules)

    b_shard = batch_shardings(cfg, mesh, shape)
    # every metric is a replicated scalar (a prefix of the metrics dict)
    metrics_sh = NamedSharding(mesh, P())
    jitted = jax.jit(fn, donate_argnums=(0, 1),
                     in_shardings=(p_shard, opt_sh, b_shard),
                     out_shardings=(metrics_sh, p_shard, opt_sh))
    nodonate = jax.jit(fn, in_shardings=(p_shard, opt_sh, b_shard),
                       out_shardings=(metrics_sh, p_shard, opt_sh))
    return TrainStep(fn=jitted, fn_nodonate=nodonate, model=model, specs=specs,
                     param_shardings=p_shard, opt_shardings=opt_sh,
                     batch_shardings=b_shard,
                     rules=rules, plan=plan, strategy=strategy, mesh=mesh,
                     scatter_mask=scatter_mask)


# ---------------------------------------------------------------------------
# DDP step (multi-process data parallelism over the active-message fabric)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DDPStep:
    """Split train step for fabric DDP (DESIGN.md §11).

    Unlike :class:`TrainStep` - one jit that exchanges gradients with
    XLA collectives inside ``shard_map`` - DDP over the active-message
    wire needs the exchange OUTSIDE jax: ``grad_fn`` produces the local
    loss plus fused f32 gradient buckets (``grad_plan``), the ring
    all-reduce sums them across localities, and ``apply_fn`` applies the
    identical optimizer update to the summed-and-averaged buckets.  Both
    halves are deterministic pure functions of their inputs, which is
    what makes every locality's post-step params bitwise equal.
    """

    # buffers apply_fn donates per call (donate_argnums=(1, 2) below);
    # the phylint step-contract builder lints against this declaration
    donated_buffers = ("params", "opt")
    grad_fn: Any                 # jitted (params, batch) -> (loss, [bufs])
    apply_fn: Any                # jitted ([bufs], params, opt) -> (gnorm, params, opt)
    model: Any = None
    specs: Any = None            # ParamSpec tree
    param_shardings: Any = None
    opt_shardings: Any = None
    batch_shardings: Any = None
    grad_plan: Any = None        # FusionPlan for the wire buckets
    strategy: Any = None
    mesh: Any = None

    def init(self, key):
        """Deterministic (params, opt) - identical on every locality fed
        the same key."""
        params = init_params(self.specs, key, self.param_shardings)
        opt = jax.device_put(optim.init(params, self.strategy.opt),
                             self.opt_shardings)
        return params, opt


def make_ddp_step(cfg=None, mesh=None, strategy: Optional[Strategy] = None,
                  shape: Optional[dict] = None, *, plan=None) -> DDPStep:
    """Build the split grad/apply step pair for fabric DDP.

    ``shape['global_batch']`` here is the PER-SHARD batch (the frontend
    divides ``Plan.batch`` by the shard count).  Gradient buckets come
    from ``optim.compression.make_plan`` with ``dp=1`` - the wire codec,
    not XLA, owns the data-parallel exchange.

    Raises:
        ValueError: strategy is zero1 (sharded optimizer state cannot
            ride a replicated-bucket wire), uses grad accumulation, or
            the mesh has an in-process dp axis (> 1) - fabric DDP IS the
            data parallelism; combine with model-axis sharding only.
    """
    if plan is not None:
        cfg, mesh, strategy, shape = plan.resolve(
            "train", cfg=cfg, mesh=mesh, strategy=strategy, shape=shape)
    if strategy.name == "zero1":
        raise ValueError("ddp=True cannot use the zero1 strategy: its "
                         "optimizer state is dp-sharded inside one process, "
                         "but fabric DDP replicates state per locality")
    if strategy.grad_accum > 1:
        raise ValueError("ddp=True with grad_accum > 1 is not supported "
                         "yet; raise Plan.ddp_shards instead")
    if dp_degree(mesh) > 1:
        raise ValueError("ddp=True replaces the in-process dp axes: use a "
                         "mesh with data=pod=1 (model-axis sharding is fine)")
    model = build_model(cfg)
    specs = model.specs()
    rules = default_rules(sequence_parallel=strategy.sequence_parallel)
    p_shard = param_shardings(specs, mesh, rules)
    structs = param_structs(specs)
    f32_structs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), structs)
    from ..optim import compression
    gplan = compression.make_plan(f32_structs, 1)
    oc = strategy.opt

    def loss_and_bufs(params, batch):
        with act_hook(mesh, rules):
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
        return loss.astype(jnp.float32), fusion.pack(grads, gplan)

    b_shard = batch_shardings(cfg, mesh, shape)
    repl = NamedSharding(mesh, P())
    bufs_sh = [repl for _ in gplan.buckets]
    grad_fn = jax.jit(loss_and_bufs,
                      in_shardings=(p_shard, b_shard),
                      out_shardings=(repl, bufs_sh))

    def apply(bufs, params, opt_state):
        grads = fusion.unpack(bufs, gplan)
        params, opt_state, m = optim.update(grads, opt_state, params, oc)
        return m["grad_norm"], params, opt_state

    f32_specs = optim.init_specs(specs, oc)
    opt_sh = param_shardings(f32_specs, mesh, rules)
    apply_fn = jax.jit(apply, donate_argnums=(1, 2),
                       in_shardings=(bufs_sh, p_shard, opt_sh),
                       out_shardings=(repl, p_shard, opt_sh))
    return DDPStep(grad_fn=grad_fn, apply_fn=apply_fn, model=model,
                   specs=specs, param_shardings=p_shard, opt_shardings=opt_sh,
                   batch_shardings=b_shard, grad_plan=gplan,
                   strategy=strategy, mesh=mesh)


def _opt_skeleton(oc: OptConfig):
    """PartitionSpec prefix-tree for dense optimizer state (all replicated
    over manual dp axes; 'model' sharding is auto)."""
    out = {"count": P()}
    if oc.kind == "adamw":
        out["m"], out["v"] = P(), P()
    elif oc.kind == "momentum":
        out["m"] = P()
    return out


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------
def decode_rules(cfg, mesh, shape: dict) -> ShardingRules:
    """Tiling plan for KV caches / recurrent state, adapted per cell."""
    r = default_rules()
    model_n = mesh.shape.get("model", 1)
    over = {}
    if model_n > 1 and cfg.n_kv_heads % model_n != 0:
        # GQA cache can't shard by head: tile the sequence dim instead
        over["kv_seq"] = "model"
        over["kv_heads"] = None
    if shape["global_batch"] == 1:
        # long-context single stream: spread the cache over "data" too
        if over.get("kv_seq") == "model":
            over["kv_seq"] = ("data", "model")
        else:
            over["kv_seq"] = "data"
    return r.with_overrides(**over)


@dataclasses.dataclass
class ServeStep:
    # decode donates the KV cache in place (donate_argnums=(1,) below);
    # the phylint step-contract builder lints against this declaration
    donated_buffers = ("cache",)
    fn: Any
    model: Any
    specs: Any
    param_shardings: Any
    cache_specs: Any            # None for prefill
    cache_shardings: Any
    batch_shardings: Any
    rules: ShardingRules


def make_prefill_step(cfg=None, mesh=None, strategy: Optional[Strategy] = None,
                      shape: Optional[dict] = None, *, plan=None) -> ServeStep:
    if plan is not None:
        cfg, mesh, strategy, shape = plan.resolve(
            "prefill", cfg=cfg, mesh=mesh, strategy=strategy, shape=shape)
    scfg = _serve_cfg(cfg)
    model = build_model(scfg)
    specs = model.specs()
    rules = decode_rules(scfg, mesh, shape)
    p_shard = param_shardings(specs, mesh, rules)
    S = shape["seq_len"]

    def prefill_step(params, batch):
        with act_hook(mesh, rules):
            return model.prefill(params, batch, S)

    cache_sp = model.cache_specs(shape["global_batch"], S)
    cache_sh = param_shardings(cache_sp, mesh, rules)
    jitted = jax.jit(prefill_step,
                     in_shardings=(p_shard, batch_shardings(scfg, mesh, shape)),
                     out_shardings=(NamedSharding(mesh, P()), cache_sh))
    return ServeStep(fn=jitted, model=model, specs=specs,
                     param_shardings=p_shard, cache_specs=cache_sp,
                     cache_shardings=cache_sh,
                     batch_shardings=batch_shardings(scfg, mesh, shape),
                     rules=rules)


def make_decode_step(cfg=None, mesh=None, strategy: Optional[Strategy] = None,
                     shape: Optional[dict] = None, *, plan=None) -> ServeStep:
    if plan is not None:
        cfg, mesh, strategy, shape = plan.resolve(
            "decode", cfg=cfg, mesh=mesh, strategy=strategy, shape=shape)
    scfg = _serve_cfg(cfg)
    model = build_model(scfg)
    specs = model.specs()
    rules = decode_rules(scfg, mesh, shape)
    p_shard = param_shardings(specs, mesh, rules)
    B, S = shape["global_batch"], shape["seq_len"]
    cache_sp = model.cache_specs(B, S)
    cache_sh = param_shardings(cache_sp, mesh, rules)

    def decode_step(params, cache, batch, pos):
        with act_hook(mesh, rules):
            return model.decode_step(params, cache, batch, pos)

    jitted = jax.jit(
        decode_step,
        in_shardings=(p_shard, cache_sh, batch_shardings(scfg, mesh, shape),
                      NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, P()), cache_sh),
        donate_argnums=(1,))
    return ServeStep(fn=jitted, model=model, specs=specs,
                     param_shardings=p_shard, cache_specs=cache_sp,
                     cache_shardings=cache_sh,
                     batch_shardings=batch_shardings(scfg, mesh, shape),
                     rules=rules)


def make_step(cfg=None, mesh=None, strategy: Optional[Strategy] = None,
              shape: Optional[dict] = None, *, plan=None):
    if shape is None:
        raise ValueError(
            "make_step dispatches on shape['kind']: pass shape= (or call "
            "make_train_step/make_prefill_step/make_decode_step)")
    kind = shape["kind"]
    if kind == "train":
        return make_train_step(cfg, mesh, strategy, shape, plan=plan)
    if kind == "prefill":
        return make_prefill_step(cfg, mesh, strategy, shape, plan=plan)
    if kind == "decode":
        return make_decode_step(cfg, mesh, strategy, shape, plan=plan)
    raise ValueError(kind)
