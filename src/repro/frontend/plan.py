"""Plan/Session: the declarative frontend over the futurized runtime.

A ``Plan`` is the *what* of a run - architecture, mesh axes, strategy,
shapes - a frozen value that touches no device state.  ``plan.compile()``
builds a ``Session``: the mesh is made, step functions are jitted lazily,
and ONE futurized runtime (`core/futures.py`) owns every host-side task of
the session - prefetch, metric forcing, checkpoint I/O, serve wave prep and
the decode chain.  ``session.train`` / ``session.serve`` subsume the old
launcher bodies; ``launch/{train,serve}.py`` are now thin argparse shims
over this API (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.checkpoint import CheckpointManager
from ..configs import get_config
from ..core import steps as steps_lib
from ..core.futures import FuturizedGraph, Lane, Pipeline
from ..core.resilience import ResilientRunner
from ..core.sharding import init_params
from ..data.pipeline import Prefetcher, stream_for
from ..launch.mesh import make_local_mesh
from .futurize import Trace

__all__ = ["Plan", "Session"]


def _stack_wave(wave):
    """Host prep of one serve wave (module-level: ships to a worker
    locality by reference when ``plan.localities > 1``)."""
    return np.stack(wave)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Declarative run description: arch + mesh axes + strategy + shapes.

    A frozen value - building one touches no device state; compile it
    with ``compile()`` to get a runnable ``Session``.

    Fields:
        arch: architecture id from ``configs.ARCH_IDS``.
        tiny: use the reduced smoke-scale config.
        data, model, pod: mesh axis sizes over the local devices.
        strategy: a ``core.steps.Strategy`` or a bare name
            ("phylanx" | "horovod" | "zero1" | "onebit").
        batch, seq: global batch and sequence length.
        seed: PRNG seed for params and synthetic streams.
        remat: enable rematerialization on tiny configs.
        localities: total process count for the multi-locality runtime
            (DESIGN.md §9).  1 runs everything in-process; N > 1 spawns
            N-1 worker localities at ``compile()`` and host-side graph
            nodes (prefetch builds, serve wave prep, checkpoint shard
            writes) are placed on them by lane + data affinity.  Device
            dispatch stays on the driver either way.
        spmd: multi-host SPMD mode (DESIGN.md §10; needs
            ``localities > 1``).  ``compile()`` stands up
            ``jax.distributed`` across all localities (the driver picks
            a loopback coordinator and is process 0), every process
            computes the train loop in deterministic lockstep on its
            local mesh, and checkpoints switch to addressable-shard
            serialization: each process writes only the blocks of the
            global persistence view it addresses - zero checkpoint leaf
            bytes cross the messaging layer.  Only ``session.train``
            supports this mode.
        ddp: data-parallel training over the active-message fabric
            (DESIGN.md §11).  The global batch is split into
            ``ddp_shards`` row shards; each locality computes gradients
            for its contiguous shard block, sums them across processes
            with a ring all-reduce of ``grad_codec``-encoded active
            messages, and applies the identical optimizer step - so
            parameters stay replicated without crossing the wire.
            Exclusive with ``spmd``; only ``session.train`` supports it.
        grad_codec: wire codec for the DDP gradient exchange: "fp32"
            (exact - the multi-process run is bit-identical in loss to
            a 1-locality run over the same shards) or "onebit" (1-bit
            signs + per-1024-row scales with error feedback, ~1/31 of
            the fp32 bytes).
        ddp_shards: batch shard count for ``ddp=True``; 0 means one
            shard per locality.  Must be a multiple of ``localities``
            and divide ``batch``; raise it to emulate a bigger world on
            fewer processes (the loss trajectory depends on the shard
            count, not the process count).
        ckpt_dir: checkpoint directory for ``session.train`` ("" leaves
            it to the ``ckpt_dir=`` argument).  All localities write
            their own shards into this one directory (DESIGN.md §10),
            so it must be shared across them (trivially true on one
            machine; a shared mount across hosts); worker localities
            receive it at spawn via ``PHYRAX_CKPT_DIR``.
        elastic: elastic membership + work stealing (DESIGN.md §13).
            The driver accepts dial-in joins (``--join host:port`` /
            ``Session.add_locality()``) mid-run; every locality runs the
            idle-thief steal loop, so newcomers pull work immediately;
            AGAS rebalances pinned objects toward them.  Exclusive with
            ``spmd`` and ``ddp`` (fixed-world collectives).  A
            ``DistributedGraph`` exists even with ``localities=1`` so a
            1-process run can scale out.
        elastic_port: fixed driver listen port for ``--join`` dialers
            (0 = ephemeral; only meaningful with ``elastic=True``).
        replicas: ``serve_stream`` model replicas (DESIGN.md §15).  Each
            replica is a prefill/decode pair with its own slots and
            named page cache, homed on its own locality when
            ``localities > 1``; the gateway router assigns every request
            to exactly one replica (page affinity first).  Token streams
            are bit-identical across replica counts.
        overrides: config field overrides applied last.
    """
    arch: str = "qwen3-4b"
    tiny: bool = True
    data: int = 1
    model: int = 1
    pod: int = 1
    strategy: Any = "phylanx"
    batch: int = 8
    seq: int = 64
    seed: int = 0
    remat: bool = False
    localities: int = 1                  # processes incl. the driver
    spmd: bool = False                   # jax.distributed SPMD mode (§10)
    ddp: bool = False                    # fabric data parallelism (§11)
    grad_codec: str = "fp32"             # DDP wire codec: fp32 | onebit
    ddp_shards: int = 0                  # batch shards (0 = localities)
    ckpt_dir: str = ""                   # shared checkpoint dir (§10)
    elastic: bool = False                # dial-in joins + stealing (§13)
    elastic_port: int = 0                # --join listen port (0 = any)
    replicas: int = 1                    # serve_stream model replicas (§15)
    overrides: dict = dataclasses.field(default_factory=dict)

    # -- resolution ---------------------------------------------------------
    def config(self):
        cfg = get_config(self.arch, tiny=self.tiny)
        over = dict(self.overrides)
        if self.tiny:
            over.setdefault("remat", self.remat)
        return dataclasses.replace(cfg, **over) if over else cfg

    def build_mesh(self):
        return make_local_mesh(data=self.data, model=self.model, pod=self.pod)

    def build_strategy(self) -> steps_lib.Strategy:
        if isinstance(self.strategy, steps_lib.Strategy):
            return self.strategy
        return steps_lib.Strategy(name=self.strategy)

    def shape_of(self, kind: str) -> dict:
        return {"seq_len": self.seq, "global_batch": self.batch,
                "kind": kind}

    def resolve(self, kind: str, *, cfg=None, mesh=None, strategy=None,
                shape=None) -> tuple:
        """(cfg, mesh, strategy, shape) with explicit arguments winning -
        the hook the ``core.steps`` builders call for ``plan=``."""
        return (cfg if cfg is not None else self.config(),
                mesh if mesh is not None else self.build_mesh(),
                strategy if strategy is not None else self.build_strategy(),
                shape if shape is not None else self.shape_of(kind))

    def compile(self) -> "Session":
        """Build the runnable ``Session`` for this plan (makes the mesh,
        spawns worker localities when ``localities > 1``).

        Returns:
            A ``Session``; use it as a context manager so the shutdown
            barrier (and worker teardown) always runs.
        """
        return Session(self)


class Session:
    """Compiled form of a ``Plan``: mesh + strategy + lazily-built step
    functions, and one futurized runtime for every host-side task.  Use as
    a context manager (or call ``close()``) to run the shutdown barrier.

    With ``plan.localities > 1`` the session also owns a
    ``repro.distrib.DistributedGraph`` (``self.distributed``): worker
    localities are spawned here and host-side nodes are transparently
    placed on them; ``close()`` drains the distributed graph before the
    local shutdown barrier, so worker teardown never strands a promise.
    """

    def __init__(self, plan: Plan, *, max_workers: int = 4):
        self.plan = plan
        self.cfg = plan.config()
        self.strategy = plan.build_strategy()
        self.runtime = FuturizedGraph(max_workers=max_workers,
                                      name=f"session:{plan.arch}")
        self.distributed = None
        if plan.spmd and plan.localities < 2:
            raise ValueError("Plan(spmd=True) needs localities >= 2: "
                             "SPMD mode is the multi-process path")
        if plan.ddp and plan.spmd:
            raise ValueError("Plan(ddp=True) and Plan(spmd=True) are "
                             "exclusive multi-process modes: ddp shards "
                             "the batch, spmd mirrors it")
        if plan.elastic and (plan.spmd or plan.ddp):
            raise ValueError(
                "Plan(elastic=True) does not compose with spmd or ddp: "
                "their collectives assume a fixed world; elastic "
                "membership is for the task-parallel runtime")
        if plan.ddp or plan.spmd:
            # SPMD mode must start jax.distributed before any backend, so
            # it reads the pinned platform list instead of starting one
            backend = ((jax.config.jax_platforms or "unpinned").split(",")[0]
                       if plan.spmd else jax.default_backend())
            if backend != "cpu":
                raise ValueError(
                    f"Plan(ddp=True) and Plan(spmd=True) compute on the CPU "
                    f"backend in several processes (this driver's backend: "
                    f"{backend}; SPMD needs JAX_PLATFORMS=cpu).  A chip "
                    f"belongs to one process: train across chips in one "
                    f"process with the in-mesh strategies instead - "
                    f"Plan(data=..., model=..., strategy='phylanx' | "
                    f"'horovod' | 'zero1' | 'onebit')")
        if plan.ddp:
            from ..distrib.collectives import CODECS
            if plan.grad_codec not in CODECS:
                raise ValueError(f"unknown grad_codec "
                                 f"{plan.grad_codec!r} (have: "
                                 f"{sorted(CODECS)})")
            world = max(plan.localities, 1)
            shards = plan.ddp_shards or world
            if shards % world:
                raise ValueError(f"ddp_shards={shards} must be a "
                                 f"multiple of localities={world}")
            if plan.batch % shards:
                raise ValueError(f"batch={plan.batch} must be divisible "
                                 f"by ddp_shards={shards}")
        if plan.localities > 1 or plan.elastic:
            from ..distrib import DistributedGraph
            # workers get the checkpoint dir at spawn (PHYRAX_CKPT_DIR):
            # each locality pre-creates it and writes its own shards
            # there (DESIGN.md §10)
            env = {"PHYRAX_CKPT_DIR": plan.ckpt_dir} if plan.ckpt_dir \
                else {}
            init_thread = None
            if plan.spmd:
                env, init_thread = self._start_jax_distributed(env)
            join_spec = None
            if plan.elastic:
                # dial-in joiners adopt the same environment the spawned
                # workers get (checkpoint dir, sanitizer arming...)
                join_env = dict(env)
                for k in ("PHYRAX_SANITIZE",):
                    if os.environ.get(k):
                        join_env[k] = os.environ[k]
                join_spec = {"env": join_env}
            self.distributed = DistributedGraph(
                localities=plan.localities, graph=self.runtime,
                worker_env=env or None, name=f"session:{plan.arch}",
                elastic=plan.elastic, elastic_port=plan.elastic_port,
                join_spec=join_spec)
            if init_thread is not None:
                init_thread.join(timeout=120.0)
                if init_thread.is_alive():
                    raise TimeoutError(
                        "jax.distributed.initialize did not complete "
                        "on the driver")
                if self._spmd_init_error:
                    raise self._spmd_init_error[0]
        # the mesh is built AFTER jax.distributed init (SPMD mode must
        # see the multi-process world to pick local devices)
        self.mesh = plan.build_mesh()
        self._train_step = None
        self._serve_steps: dict[tuple, tuple] = {}
        self._closed = False

    def _start_jax_distributed(self, env: dict):
        """SPMD bring-up: pick a loopback coordinator, export it to the
        workers' spawn environment, and start the driver's own
        ``jax.distributed.initialize`` (process 0) on a thread - it
        blocks until every process joins, and the workers are only
        spawned by the ``DistributedGraph`` constructed next."""
        import threading

        from ..launch.mesh import free_port, maybe_init_jax_distributed
        coord = f"127.0.0.1:{free_port()}"
        # the coordinator reaches the WORKERS via their spawn env and
        # the driver via explicit arguments: this process's os.environ
        # stays untouched, so a later non-SPMD Session in the same
        # interpreter cannot inherit a stale coordinator
        env = dict(env)
        env["PHYRAX_JAX_COORDINATOR"] = coord
        env["PHYRAX_JAX_NUM_PROCESSES"] = str(self.plan.localities)
        self._spmd_init_error: list = []

        def init():
            try:
                maybe_init_jax_distributed(
                    process_id=0, num_processes=self.plan.localities,
                    coordinator=coord)
            except BaseException as e:  # noqa: BLE001 - re-raised above
                self._spmd_init_error.append(e)

        t = threading.Thread(target=init, daemon=True,
                             name="jax-distributed-init")
        t.start()
        return env, t

    # -- lifecycle ----------------------------------------------------------
    def close(self):
        """Run the shutdown barrier: drain distributed tasks, stop worker
        localities, then drain and stop the local runtime.  In SPMD mode
        the driver also joins the ``jax.distributed`` shutdown barrier
        concurrently with telling the workers to exit - every process
        must arrive at that barrier or teardown turns fatal.
        Idempotent."""
        if not self._closed:
            self._closed = True
            if self.distributed is not None:
                jd_thread = None
                if self.plan.spmd:
                    import threading

                    def _jd_shutdown():
                        try:
                            jax.distributed.shutdown()
                        except Exception:  # noqa: BLE001 - best effort
                            pass
                    jd_thread = threading.Thread(
                        target=_jd_shutdown, daemon=True,
                        name="jax-distributed-shutdown")
                    jd_thread.start()
                self.distributed.shutdown(wait=True)
                if jd_thread is not None:
                    jd_thread.join(timeout=60.0)
            self.runtime.shutdown(wait=True)

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc):
        self.close()

    def stats(self):
        """The session runtime's ``RuntimeStats`` (see its docstring for
        the ``to_json`` schema)."""
        return self.runtime.stats()

    def lint(self, *, strict_lanes: bool = False):
        """Run the static phylint passes over this session's live graph.

        Snapshots every node the runtime still holds (in-flight and
        recently retired) and applies the PHY001-PHY006 rule set
        (DESIGN.md §12).  Works for any locality count - unlike the
        dryrun mirrors in ``repro.analysis.trace_builders``, this sees
        the promise/dispatch pairs a distributed run actually created.

        Returns:
            List of ``repro.analysis.lint.Finding``, empty when clean.
        """
        from ..analysis import lint as lint_mod

        return lint_mod.lint(lint_mod.LintGraph.from_graph(self.runtime),
                             strict_lanes=strict_lanes)

    @property
    def join_address(self) -> Optional[tuple]:
        """``(host, port)`` a ``--join`` dialer should use, or None when
        the session is not elastic."""
        if self.distributed is None or not self.plan.elastic:
            return None
        return tuple(self.distributed.endpoint.address)

    def add_locality(self, timeout: float = 120.0) -> int:
        """Elastic scale-out (DESIGN.md §13): spawn one extra worker
        locality into the *running* session and block until it is a full
        member - peers gossiped, AGAS rebalanced, steal loop armed.
        Safe to call from a training hook; subsequent steerable host
        tasks may be stolen by (or diverted to) the newcomer.

        Returns:
            The new locality's rank.
        Raises:
            RuntimeError: the session was not compiled from an elastic
                plan.
        """
        if self.distributed is None or not self.plan.elastic:
            raise RuntimeError("add_locality needs Plan(elastic=True)")
        return self.distributed.add_locality(timeout=timeout)

    def kill_locality(self, rank: Optional[int] = None) -> Optional[int]:
        """Failure drill: SIGKILL a worker locality (the highest-ranked
        alive one by default).  Its in-flight tasks re-spawn elsewhere.

        Returns:
            The killed rank, or None when no worker locality is alive.
        """
        if self.distributed is None:
            return None
        alive = self.distributed.group.alive_workers()
        if not alive:
            return None
        rank = alive[-1] if rank is None else rank
        self.distributed.group.kill(rank)
        return rank

    # -- steps --------------------------------------------------------------
    @property
    def train_step(self) -> steps_lib.TrainStep:
        if self._train_step is None:
            # already-resolved session state wins; the plan fills the shape
            self._train_step = steps_lib.make_train_step(
                self.cfg, self.mesh, self.strategy, plan=self.plan)
        return self._train_step

    def _serve_steps_for(self, prompt_len: int, gen_len: int, slots: int):
        if self.cfg.mla:
            raise ValueError(
                f"{self.cfg.name}: serving latent attention (MLA) is not "
                f"supported - there is no latent decode cache; MLA models "
                f"train only (Session.train)")
        key = (prompt_len, gen_len, slots)
        if key not in self._serve_steps:
            cache_len = prompt_len + gen_len
            pre = steps_lib.make_prefill_step(
                self.cfg, self.mesh, self.strategy,
                {"seq_len": cache_len, "global_batch": slots,
                 "kind": "prefill"})
            dec = steps_lib.make_decode_step(
                self.cfg, self.mesh, self.strategy,
                {"seq_len": cache_len, "global_batch": slots,
                 "kind": "decode"})
            self._serve_steps[key] = (pre, dec)
        return self._serve_steps[key]

    # -- train --------------------------------------------------------------
    def train(self, stream=None, *, steps: int = 50, hooks: Any = None,
              ckpt_dir: Optional[str] = None, ckpt_every: int = 20,
              log_every: int = 5,
              resume: bool = False, fail_at_step: Optional[int] = None,
              kill_locality_at_step: Optional[int] = None,
              resilience: str = "none", verbose: bool = True) -> dict:
        """The training loop the old ``launch/train.py`` hand-wired: stream
        -> prefetch nodes -> step -> in-flight pipeline -> async checkpoint
        nodes, all on the session runtime.  With ``plan.localities > 1``
        the prefetch *builds* run on worker localities and stream back;
        placement and device dispatch stay here, so the loss trajectory
        is identical to the single-process run.

        With ``plan.ddp=True`` the body is the fabric-DDP loop instead
        (DESIGN.md §11): every locality - the driver included - trains
        its own shard block of the batch and gradients are summed over
        the active-message ring; the result dict (and the report's
        ``grad-wire`` line) gains ``grad_wire_bytes``, the exact
        gradient payload bytes the driver sent.

        Args:
            stream: object with ``batch_at(step) -> dict``; defaults to
                the architecture's synthetic stream (``stream_for``).
                Must be picklable when localities > 1.
            steps: total step count (absolute, not incremental).
            hooks: any object with optional ``on_step(it, metrics)``,
                ``on_log(it, loss)`` and ``on_checkpoint(step, future)``
                methods.
            ckpt_dir: checkpoint directory (defaults to
                ``plan.ckpt_dir``; empty disables snapshots).  With
                ``plan.localities > 1`` every save is split into
                locality-owned shards written by their owners as
                CHECKPOINT-lane tasks, and resumes read shards across
                the current localities - including a checkpoint written
                by a different locality count (DESIGN.md §10).
            ckpt_every / log_every: cadence in steps.
            resume: restore the latest checkpoint in ``ckpt_dir`` first.
            fail_at_step: drill seam - raise an injected node failure at
                this step (ignored under ``resume``).
            kill_locality_at_step: drill seam - SIGKILL a worker
                locality at this step; training must survive via task
                re-spawn (no-op when localities == 1).
            resilience: "none" | "replay" | "replicate" (HPX-style step
                resilience, ``core.resilience``).
            verbose: print progress and the final runtime report.
        Returns:
            dict with ``final_loss``, per-log ``losses``, ``params``,
            ``step``, and ``runtime_stats`` (the documented
            ``RuntimeStats.to_json`` schema, plus ``distributed`` when
            localities > 1).
        Raises:
            RuntimeError: the injected failure of ``fail_at_step``.
        """
        if self.plan.ddp:
            return self._train_ddp(
                stream, steps=steps, hooks=hooks, ckpt_dir=ckpt_dir,
                ckpt_every=ckpt_every, log_every=log_every, resume=resume,
                fail_at_step=fail_at_step,
                kill_locality_at_step=kill_locality_at_step,
                resilience=resilience, verbose=verbose)
        plan, runtime, step = self.plan, self.runtime, self.train_step
        spmd_mode = plan.spmd and self.distributed is not None
        if spmd_mode and resilience != "none":
            raise ValueError("resilience modes are not mirrored by the "
                             "SPMD shadow loop; use resilience='none' "
                             "with Plan(spmd=True)")
        if spmd_mode and kill_locality_at_step is not None:
            raise ValueError(
                "kill_locality_at_step is a multi-locality drill: a "
                "jax.distributed world does not survive losing a "
                "process (coordination-service teardown is collective). "
                "Drill SPMD host loss with fail_at_step + a --resume "
                "run on a different process count instead")
        if ckpt_dir is None:
            ckpt_dir = plan.ckpt_dir
        if stream is None:
            stream = stream_for(self.cfg, batch=plan.batch, seq=plan.seq,
                                seed=plan.seed)
        params, opt = step.init(jax.random.PRNGKey(plan.seed))
        start = 0

        ckpt = (CheckpointManager(ckpt_dir, keep=3, graph=runtime,
                                  dgraph=self.distributed)
                if ckpt_dir else None)
        if ckpt is not None and resume:
            latest = ckpt.latest_step()
            if latest is not None:
                start, (params, opt) = ckpt.restore(
                    (params, opt),
                    shardings=(step.param_shardings, step.opt_shardings))
                if verbose:
                    print(f"[train] resumed from step {start}")

        if spmd_mode:
            # every worker process mirrors this loop in lockstep and
            # writes its own addressable checkpoint shards (DESIGN.md
            # §10); batches build locally on each process, so nothing
            # here is deferred to workers
            self.distributed.spmd_train({
                "plan": plan, "steps": steps, "ckpt_every": ckpt_every,
                "ckpt_dir": ckpt_dir, "resume": resume, "stream": stream})
        prefetch = Prefetcher(stream, step.batch_shardings, graph=runtime,
                              dgraph=None if spmd_mode
                              else self.distributed)
        runner = (ResilientRunner(step.fn_nodonate)
                  if resilience in ("replay", "replicate") else None)
        inflight = Pipeline(depth=2)
        log_futs: list = []
        t_log = time.time()
        on_step = getattr(hooks, "on_step", None)
        on_log = getattr(hooks, "on_log", None)
        on_ckpt = getattr(hooks, "on_checkpoint", None)

        def _force_and_log(it, m, t_start):
            # Runs on a runtime worker: forcing metrics never stalls dispatch.
            loss = float(m["loss"])
            dt = (time.time() - t_start) / log_every
            if verbose:
                print(f"[train] step {it + 1:5d} loss {loss:8.4f} "
                      f"gnorm {float(m['grad_norm']):8.3f} "
                      f"{dt * 1e3:8.1f} ms/step", flush=True)
            if on_log is not None:
                on_log(it, loss)
            return loss

        metrics = None
        try:
            for it in range(start, steps):
                if kill_locality_at_step is not None \
                        and it == kill_locality_at_step:
                    killed = self.kill_locality()
                    if verbose and killed is not None:
                        print(f"[train] drill: killed locality {killed} "
                              f"at step {it}", flush=True)
                batch = prefetch.get(it)
                if fail_at_step is not None and it == fail_at_step \
                        and not resume:
                    raise RuntimeError(f"injected node failure at step {it}")
                if resilience == "replay":
                    metrics, params, opt = runner.replay(params, opt, batch)
                elif resilience == "replicate":
                    metrics, params, opt = runner.replicate(params, opt,
                                                            batch, n=2)
                else:
                    metrics, params, opt = step.fn(params, opt, batch)
                inflight.push(it, metrics)
                if on_step is not None:
                    on_step(it, metrics)
                if (it + 1) % log_every == 0:
                    # CHECKPOINT lane: forcing metrics for logs must never
                    # outrank the PREFETCH nodes the loop blocks on next
                    log_futs.append(runtime.defer(
                        _force_and_log, it, metrics, t_log,
                        lane=Lane.CHECKPOINT, name=f"log:{it}"))
                    t_log = time.time()
                if ckpt is not None and (it + 1) % ckpt_every == 0:
                    # The write node depends on step retirement: file I/O
                    # starts only after the step's outputs resolve on device.
                    retired = runtime.defer(jax.block_until_ready, metrics,
                                            lane=Lane.CHECKPOINT,
                                            name=f"retire:{it}")
                    fut = ckpt.save(it + 1, (params, opt), deps=(retired,),
                                    meta={"arch": plan.arch})
                    if on_ckpt is not None:
                        on_ckpt(it + 1, fut)
            inflight.drain()
            # final snapshot - unless the loop's cadence already saved
            # this exact step (no duplicate serialize/ship/write, and no
            # rmtree+rename window over a just-committed directory)
            if ckpt is not None and steps % ckpt_every != 0:
                ckpt.save(steps, (params, opt), meta={"arch": plan.arch})
        finally:
            # Shutdown barrier - also on the injected-failure path, so a
            # crash never loses a save that was already requested: retire
            # in-flight steps, land every pending checkpoint node.  The
            # runtime itself stays up: it belongs to the session.
            inflight.drain()
            prefetch.close()       # cancel batches nobody will consume
            if ckpt is not None:
                ckpt.close()
            runtime.barrier()

        if spmd_mode:
            # the shadows have posted every entry this run's saves needed
            # (ckpt.close() waited on the commits); now surface a shadow
            # that FAILED - its checkpoints were silently aborted
            done = self.distributed.wait_spmd_done(timeout=600.0)
            failed = [m for m in done.values() if not m.get("ok")]
            if failed:
                raise RuntimeError(
                    f"SPMD shadow train loop failed on locality "
                    f"{failed[0]['rank']}: {failed[0].get('error')}")

        losses = [f.result() for f in log_futs]
        st = runtime.stats()
        stats_json = st.to_json()
        dstats = (self.distributed.stats()
                  if self.distributed is not None else None)
        if dstats is not None:
            stats_json["distributed"] = dstats
        if metrics is None:    # resumed at/after steps: nothing left to run
            if verbose:
                print(f"[train] nothing to do: resumed at step {start} "
                      f">= steps {steps}")
            return {"final_loss": float("nan"), "losses": losses,
                    "params": params, "step": start,
                    "runtime_stats": stats_json}
        final = float(metrics["loss"])
        if verbose:
            print(f"[train] done: final loss {final:.4f} "
                  f"(host tasks {st.completed}, "
                  f"max in-flight {st.max_in_flight})")
            hist = stats_json["lane_time_hist"]
            print(f"[train] task wall-time buckets "
                  f"{' '.join(hist['labels'])} "
                  f"(edges_s={hist['edges_s']})")
            for line in st.hist_lines():
                print(f"[train] task wall-time {line}")
            if dstats is not None:
                print(f"[train] localities: dispatched "
                      f"{dstats['dispatched']} respawned "
                      f"{dstats['respawned']} wire "
                      f"{dstats['bytes_sent']}B out / "
                      f"{dstats['bytes_recv']}B in "
                      f"ckpt-leaf-wire {dstats['ckpt_leaf_wire_bytes']}B")
                if self.plan.elastic:
                    print(f"[train] elastic: joined "
                          f"{dstats['joined_localities']} stolen "
                          f"{dstats['stolen_tasks']} migrated "
                          f"{dstats['migrated_objects']} objects "
                          f"(membership gen "
                          f"{dstats['membership_gen']})")
            if ckpt is not None and ckpt.aborted_saves:
                print(f"[train] WARNING: {ckpt.aborted_saves} SPMD "
                      f"save(s) aborted with a lost writer; the last "
                      f"committed checkpoint is step "
                      f"{ckpt.latest_step()}")
        return {"final_loss": final, "losses": losses,
                "params": params, "step": steps,
                "runtime_stats": stats_json}

    def _train_ddp(self, stream, *, steps, hooks, ckpt_dir, ckpt_every,
                   log_every, resume, fail_at_step, kill_locality_at_step,
                   resilience, verbose) -> dict:
        """The ``Plan(ddp=True)`` body of ``train`` (DESIGN.md §11): the
        driver is ring rank 0 and trains its own shard block in-process
        while ``ddp_train`` active messages start the same loop
        (``frontend.ddp.ddp_shadow_train``) on every worker locality.
        Checkpoints are driver-only - parameters are replicated, so the
        driver's save IS the global state; a failure anywhere poisons
        the ring (``ddp_abort``), so no locality ever hangs."""
        from ..distrib.collectives import RingAllReduce
        from .ddp import DDPEngine
        plan, runtime = self.plan, self.runtime
        if resilience != "none":
            raise ValueError("resilience modes do not compose with "
                             "Plan(ddp=True): the ring's abort-on-loss "
                             "failure model replaces step replay")
        if ckpt_dir is None:
            ckpt_dir = plan.ckpt_dir
        if stream is None:
            stream = stream_for(self.cfg, batch=plan.batch, seq=plan.seq,
                                seed=plan.seed)
        ring = (self.distributed.grad_ring
                if self.distributed is not None else RingAllReduce(None, 1))
        engine = DDPEngine(plan, ring)
        step = engine.step
        params, opt = engine.init()
        start = 0
        ckpt = (CheckpointManager(ckpt_dir, keep=3, graph=runtime)
                if ckpt_dir else None)
        if ckpt is not None and resume:
            if ckpt.latest_step() is not None:
                start, (params, opt) = ckpt.restore(
                    (params, opt),
                    shardings=(step.param_shardings, step.opt_shardings))
                if verbose:
                    print(f"[train] resumed from step {start}")
        if self.distributed is not None:
            self.distributed.ddp_train({
                "plan": plan, "steps": steps, "ckpt_dir": ckpt_dir,
                "resume": resume, "stream": stream, "gen": ring.gen})
        # no shardings: the driver slices its own shards from the raw
        # host batch, exactly as the workers do
        prefetch = Prefetcher(stream, None, graph=runtime)
        on_step = getattr(hooks, "on_step", None)
        on_log = getattr(hooks, "on_log", None)
        on_ckpt = getattr(hooks, "on_checkpoint", None)
        losses: list = []
        t_log = time.time()
        metrics = None
        try:
            for it in range(start, steps):
                if kill_locality_at_step is not None \
                        and it == kill_locality_at_step:
                    killed = self.kill_locality()
                    if verbose and killed is not None:
                        print(f"[train] drill: killed locality "
                              f"{killed} at step {it}", flush=True)
                batch = prefetch.get(it)
                if fail_at_step is not None and it == fail_at_step \
                        and not resume:
                    raise RuntimeError(
                        f"injected node failure at step {it}")
                metrics, params, opt = engine.train_step(
                    it, batch, params, opt)
                if on_step is not None:
                    on_step(it, metrics)
                if (it + 1) % log_every == 0:
                    loss = float(metrics["loss"])
                    losses.append(loss)
                    dt = (time.time() - t_log) / log_every
                    if verbose:
                        print(f"[train] step {it + 1:5d} loss "
                              f"{loss:8.4f} gnorm "
                              f"{float(metrics['grad_norm']):8.3f} "
                              f"{dt * 1e3:8.1f} ms/step", flush=True)
                    if on_log is not None:
                        on_log(it, loss)
                    t_log = time.time()
                if ckpt is not None and (it + 1) % ckpt_every == 0:
                    retired = runtime.defer(
                        jax.block_until_ready, metrics["grad_norm"],
                        lane=Lane.CHECKPOINT, name=f"retire:{it}")
                    fut = ckpt.save(it + 1, (params, opt),
                                    deps=(retired,),
                                    meta={"arch": plan.arch})
                    if on_ckpt is not None:
                        on_ckpt(it + 1, fut)
            if ckpt is not None and steps % ckpt_every != 0 \
                    and metrics is not None:
                ckpt.save(steps, (params, opt), meta={"arch": plan.arch})
        except BaseException:
            # poison the ring everywhere: workers blocked in an
            # all-reduce must abort, not wait out their timeout
            if self.distributed is not None:
                self.distributed.ddp_abort("the driver aborted the DDP run")
            raise
        finally:
            prefetch.close()
            if ckpt is not None:
                ckpt.close()
            runtime.barrier()
            ring.deactivate()

        if self.distributed is not None:
            done = self.distributed.wait_ddp_done(timeout=600.0)
            failed = [m for m in done.values() if not m.get("ok")]
            if failed:
                raise RuntimeError(
                    f"DDP train loop failed on locality "
                    f"{failed[0]['rank']}: {failed[0].get('error')}")
        st = runtime.stats()
        stats_json = st.to_json()
        dstats = (self.distributed.stats()
                  if self.distributed is not None else None)
        if dstats is not None:
            stats_json["distributed"] = dstats
        gwb = (dstats["grad_wire_bytes"] if dstats is not None
               else int(ring.wire_bytes))
        final = (float(metrics["loss"]) if metrics is not None
                 else float("nan"))
        if verbose:
            if metrics is None:
                print(f"[train] nothing to do: resumed at step {start} "
                      f">= steps {steps}")
            else:
                print(f"[train] done: final loss {final:.4f} "
                      f"(ddp world {engine.world}, "
                      f"shards {engine.shards})")
            print(f"[train] grad-wire {gwb}B ({plan.grad_codec} codec, "
                  f"{engine.codec_bytes}B/locality/exchange)")
            if dstats is not None:
                print(f"[train] localities: wire "
                      f"{dstats['bytes_sent']}B out / "
                      f"{dstats['bytes_recv']}B in")
        return {"final_loss": final, "losses": losses, "params": params,
                "step": steps if metrics is not None else start,
                "grad_wire_bytes": gwb, "codec_bytes": engine.codec_bytes,
                "runtime_stats": stats_json}

    # -- serve --------------------------------------------------------------
    def serve(self, requests: int = 8, *, prompt_len: int = 32,
              gen_len: int = 16, slots: int = 4, prompts=None,
              verbose: bool = True) -> dict:
        """Batched prefill + decode with slot refill, as a futurized tree:
        each wave is a ``prefill`` node plus ``gen_len`` chained ``decode``
        nodes (dependency edges carry the (token, cache) pair), while the
        next wave's host prep runs as a PREFETCH node - on a worker
        locality when ``plan.localities > 1``, with placement and device
        work staying on the driver.

        Args:
            requests: request count when ``prompts`` is None (otherwise
                ``len(prompts)`` wins).
            prompt_len: tokens per prompt (synthetic prompts only).
            gen_len: decode steps per request.
            slots: decode slots per wave (idle slots are padded).
            prompts: optional list of int32 token arrays.
            verbose: print the throughput summary line.
        Returns:
            dict with ``tokens_per_s``, ``requests``, ``tokens``, the
            traced node ``nodes``/``trace`` (decode steps are explicit,
            named graph nodes), and ``runtime_stats``.
        """
        plan, runtime, cfg = self.plan, self.runtime, self.cfg
        pre, dec = self._serve_steps_for(prompt_len, gen_len, slots)
        params = init_params(pre.specs, jax.random.PRNGKey(plan.seed),
                             pre.param_shardings)

        if prompts is None:
            rng = np.random.default_rng(plan.seed)
            prompts = [rng.integers(0, cfg.vocab,
                                    prompt_len).astype(np.int32)
                       for _ in range(requests)]
        waiting = list(prompts)
        requests = len(waiting)
        if not waiting:        # nothing to serve: no dummy wave, no tokens
            return {"tokens_per_s": 0.0, "requests": 0, "tokens": 0,
                    "padded_tokens": 0, "nodes": [], "trace": [],
                    "runtime_stats": self.runtime.stats().to_json()}
        tok_sh = dec.batch_shardings["tokens"]

        def prepare_wave(wave) -> dict:
            # wave: list of prompt arrays, or the already-stacked ndarray
            # a worker locality streamed back (np.stack handles both)
            toks = jax.device_put(jnp.asarray(np.stack(wave)),
                                  pre.batch_shardings["tokens"])
            batch = {"tokens": toks}
            if cfg.family == "encdec":
                batch["frames"] = jnp.zeros(
                    (slots, cfg.enc_frames, cfg.d_model), cfg.c_dtype)
            return batch

        def defer_wave(wave, w: int):
            # multi-locality: the host prep (stacking the prompt arrays)
            # runs on a worker and streams back; placement stays local
            # under the same "wave:{w}" node name either way
            if self.distributed is not None:
                stacked = self.distributed.defer(
                    _stack_wave, wave, lane=Lane.PREFETCH,
                    name=f"stack:{w}")
                return runtime.defer(prepare_wave, stacked,
                                     lane=Lane.PREFETCH, name=f"wave:{w}")
            return runtime.defer(prepare_wave, wave, lane=Lane.PREFETCH,
                                 name=f"wave:{w}")

        def take_wave() -> tuple[list, int]:
            wave = [waiting.pop() for _ in range(min(slots, len(waiting)))]
            n_real = len(wave)
            while len(wave) < slots:            # pad idle slots
                wave.append(np.zeros(prompt_len, np.int32))
            return wave, n_real

        padded_out = 0

        def _prefill(batch, *_prev_tail):
            # *_prev_tail: dispatch-order edge from the previous wave's last
            # decode node; its value is unused
            logits, cache = pre.fn(params, batch)
            tok = jax.device_put(
                jnp.argmax(logits, -1)[:, None].astype(jnp.int32), tok_sh)
            return tok, cache

        def _decode(carry, pos):
            tok, cache = carry
            logits, cache = dec.fn(params, cache, {"tokens": tok}, pos)
            tok = jax.device_put(
                jnp.argmax(logits, -1)[:, None].astype(jnp.int32), tok_sh)
            return tok, cache

        tracer = Trace(runtime)
        remove = runtime.add_trace_hook(tracer.record)
        done, tokens_out, w = 0, 0, 0
        t0 = time.time()
        try:
            wave, n_real = take_wave()
            batch_fut = defer_wave(wave, 0)
            tail = None
            while True:
                nxt = None
                if waiting and done + n_real < requests:
                    next_wave, next_real = take_wave()
                    nxt = (defer_wave(next_wave, w + 1), next_real)
                # The wave's futurized tree, built up-front: nothing below
                # forces a transfer, so prefill and every decode step stay
                # in flight back-to-back under JAX async dispatch.
                deps = (batch_fut,) if tail is None else (batch_fut, tail)
                carry = runtime.defer(_prefill, *deps, name=f"prefill:w{w}")
                for t in range(gen_len):
                    carry = runtime.defer(_decode, carry,
                                          jnp.int32(prompt_len + t),
                                          name=f"decode:w{w}:t{t}")
                tail = carry
                # padded idle slots decode too, but their tokens are not
                # throughput: account them separately so latency/throughput
                # numbers aren't diluted by padding (RuntimeStats "serve")
                tokens_out += n_real * gen_len
                padded_out += (slots - n_real) * gen_len
                runtime.record_serve(
                    real_tokens=n_real * gen_len,
                    padded_slot_tokens=(slots - n_real) * gen_len)
                done += n_real
                if nxt is None:
                    break
                batch_fut, n_real = nxt
                w += 1
            last_tok, _ = tail.result()
            jax.block_until_ready(last_tok)   # honest timing: retire it all
        finally:
            remove()
        dt = time.time() - t0
        tps = tokens_out / dt
        st = runtime.stats()
        stats_json = st.to_json()
        if self.distributed is not None:
            stats_json["distributed"] = self.distributed.stats()
        nodes = tracer.names()
        n_decode = sum(n.startswith("decode:") for n in nodes)
        if verbose:
            print(f"[serve] {requests} requests, {tokens_out} tokens in "
                  f"{dt:.2f}s -> {tps:.1f} tok/s (slots={slots}, "
                  f"padded {padded_out}, decode nodes {n_decode}, "
                  f"host tasks {st.completed})")
        return {"tokens_per_s": tps, "requests": requests,
                "tokens": tokens_out, "padded_tokens": padded_out,
                "nodes": nodes,
                "trace": tracer.signature(), "runtime_stats": stats_json}

    # -- serve (gateway) -----------------------------------------------------
    def serve_stream(self, requests: int = 8, *, prompt_len: int = 32,
                     gen_len: int = 16, slots: int = 4,
                     max_inflight: Optional[int] = None,
                     deadline_ms: Optional[float] = None,
                     trace=None, queue=None, page_bytes: int = 1 << 16,
                     replicas: Optional[int] = None,
                     kill_replica_at_round: Optional[tuple] = None,
                     verbose: bool = True) -> dict:
        """The serving gateway (DESIGN.md §14): async continuous batching
        with mid-flight arrivals, admission control and the paged
        inference cache, instead of ``serve``'s synchronized waves.

        Each request is a first-class node chain (``stack`` -> ``prefill``
        -> ``refill``/``decode``/``emit`` -> ``finish`` resolving its
        ``request:{rid}`` promise); prefill runs once at admission and its
        decode state parks in ``core.paging.InferenceCache`` pages until a
        slot frees, so slot refill never recomputes prefill.

        Args:
            requests: synthetic request count when neither ``trace`` nor
                ``queue`` is given (all arriving at round 0).
            prompt_len, gen_len, slots: as for ``serve``.
            max_inflight: admission cap on requests holding resources
                (queued + decoding); defaults to ``2 * slots``.
            deadline_ms: default per-request deadline; a request still
                short of a slot when it lapses expires cleanly.
            trace: deterministic arrival script - a list of dicts with
                optional ``prompt``, ``at_round`` (decode round of
                arrival), ``deadline_ms``, ``cancel_after`` (cancel after
                that many decoded tokens), ``inject``
                (``"poison-prefill"``).
            queue: a live ``gateway.RequestQueue`` fed from other
                threads; the gateway drains it until ``close()``.
            page_bytes: page size of the inference cache pool (shared
                across replicas; each replica owns a named cache on it).
            replicas: model replica count (defaults to ``plan.replicas``).
                Each replica gets its own ``slots``-wide decode chain and
                the router spreads requests across them (DESIGN.md §15);
                per-request streams are bit-identical to ``replicas=1``.
            kill_replica_at_round: deterministic replica-death drill -
                ``(replica_idx, round)`` marks that replica dead at that
                decode round; survivors absorb its requests.
            verbose: print the summary line.
        Returns:
            dict with per-request ``streams``/``handles``, admission
            counts, ``tokens``/``padded_tokens``/``tokens_per_s``, the
            traced ``nodes``/``trace``, ``replicas``/
            ``replica_assignments`` and ``runtime_stats`` (including the
            ``serve``/``serve_replicas`` counters and
            ``request_latency_hist``).
        """
        from .gateway import Gateway, RequestQueue
        plan, runtime, cfg = self.plan, self.runtime, self.cfg
        n_replicas = plan.replicas if replicas is None else int(replicas)
        if cfg.family == "encdec":
            raise ValueError("serve_stream does not support encdec "
                             "architectures (scalar-only decoder position "
                             "embedding); use serve()")
        pre1 = self._serve_steps_for(prompt_len, gen_len, 1)[0]
        dec = self._serve_steps_for(prompt_len, gen_len, slots)[1]
        params = init_params(pre1.specs, jax.random.PRNGKey(plan.seed),
                             pre1.param_shardings)

        if queue is None:
            q = RequestQueue()
            entries = trace if trace is not None \
                else [{"at_round": 0} for _ in range(requests)]
            rng = np.random.default_rng(plan.seed)
            for e in entries:
                prompt = e.get("prompt")
                if prompt is None:
                    prompt = rng.integers(0, cfg.vocab,
                                          prompt_len).astype(np.int32)
                q.submit(prompt, at_round=e.get("at_round", 0),
                         deadline_ms=e.get("deadline_ms", deadline_ms),
                         cancel_after=e.get("cancel_after"),
                         inject=e.get("inject"))
            q.close()
        else:
            q = queue

        gw = Gateway(runtime, distributed=self.distributed,
                     prefill_step=pre1, decode_step=dec, params=params,
                     prompt_len=prompt_len, gen_len=gen_len, slots=slots,
                     max_inflight=max_inflight, deadline_ms=deadline_ms,
                     page_bytes=page_bytes, replicas=n_replicas,
                     kill_replica_at_round=kill_replica_at_round)
        self._gateway = gw          # drill seam: tests call kill_replica()
        tracer = Trace(runtime)
        remove = runtime.add_trace_hook(tracer.record)
        t0 = time.time()
        try:
            out = gw.run(q)
        finally:
            remove()
        dt = time.time() - t0
        tokens = sum(max(0, len(h.tokens) - 1) for h in out["handles"])
        st = runtime.stats()
        stats_json = st.to_json()
        if self.distributed is not None:
            stats_json["distributed"] = self.distributed.stats()
        out.update({
            "requests": q.submitted, "tokens": tokens,
            "padded_tokens": st.serve.get("padded_slot_tokens", 0),
            "tokens_per_s": tokens / dt if dt > 0 else 0.0,
            "nodes": tracer.names(), "trace": tracer.signature(),
            "runtime_stats": stats_json,
        })
        if verbose:
            rep_note = (f" across {n_replicas} replicas"
                        if n_replicas > 1 else "")
            print(f"[gateway] {q.submitted} requests{rep_note} "
                  f"({out['completed']} done, {out['cancelled']} "
                  f"cancelled, {out['expired']} expired, "
                  f"{out['failed']} failed, {out['rejected']} rejected), "
                  f"{tokens} tokens in {dt:.2f}s -> "
                  f"{out['tokens_per_s']:.1f} tok/s over {out['epochs']} "
                  f"epochs (page hits {st.serve.get('page_hits', 0)}/"
                  f"{st.serve.get('refills', 0)} refills)")
        return out
