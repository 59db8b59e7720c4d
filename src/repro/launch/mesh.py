"""Mesh construction and multi-process device bring-up.  Everything here
is a FUNCTION, not a module-level constant: importing this module never
touches jax device state.

Multi-process note: when the multi-locality runtime (``repro.distrib``)
spawns worker processes, each worker calls ``maybe_init_jax_distributed``
before any device work.  It is a no-op unless ``PHYRAX_JAX_COORDINATOR``
is set, because the CPU-only CI path runs each locality on its *own*
local jax (host tasks only, no cross-process device collectives) and
must not stand up a coordination service it never uses.
"""
from __future__ import annotations

import os
import pathlib
import socket

import jax
from jax.sharding import AxisType


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache for an entry point's
    process and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, so a later run of the same
    checkout finds what an earlier one compiled.  Entry points call this
    in ``__main__``; library code and ``Session`` never do.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    checkout = pathlib.Path(__file__).resolve().parents[3]
    path = str(checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], *,
              devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 1):
    """Mesh over the local devices for the frontend, tests and examples.

    Under ``jax.distributed`` (SPMD mode) the mesh is built from THIS
    process's ``jax.local_devices()`` only: the CPU backend cannot run
    multi-process computations, so every process computes on an
    identical local mesh in lockstep and only *persistence* spans
    processes (``checkpoint.spmd``, DESIGN.md §10)."""
    shape, axes = [], []
    for n, a in ((pod, "pod"), (data, "data"), (model, "model")):
        if n > 1 or a in ("data", "model"):
            shape.append(n)
            axes.append(a)
    devices = None
    if jax.process_count() > 1:
        devices = jax.local_devices()
    return make_mesh(tuple(shape), tuple(axes), devices=devices)


def free_port() -> int:
    """An ephemeral loopback port for the ``jax.distributed``
    coordinator of a single-machine SPMD run (the OS-assigned port is
    released before returning; the race window is acceptable for tests
    and drills)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def maybe_init_jax_distributed(*, process_id: int | None = None,
                               num_processes: int | None = None,
                               coordinator: str | None = None) -> bool:
    """Initialize ``jax.distributed`` for a spawned multi-process run.

    Reads ``PHYRAX_JAX_COORDINATOR`` (``host:port`` of process 0) plus
    optional ``PHYRAX_JAX_NUM_PROCESSES`` / ``PHYRAX_JAX_PROCESS_ID``
    overrides; explicit arguments win over the environment.  Returns
    False without touching jax unless a coordinator is configured - the
    CPU / single-process path must stay cold.

    Args:
        process_id: this process's rank (defaults to the env override).
        num_processes: world size (defaults to the env override).
        coordinator: ``host:port`` of process 0 (defaults to the env
            gate; an SPMD ``Session`` passes it explicitly so the
            driver process's environment is never mutated).
    Returns:
        True if ``jax.distributed.initialize`` was called.
    Raises:
        ValueError: a coordinator is configured but the world size is
            not (set ``PHYRAX_JAX_NUM_PROCESSES`` or pass
            ``num_processes``) - half-configured must be loud, not a
            guaranteed-wrong ``initialize(num_processes=0)``.
        RuntimeError: initialization was configured but failed (surfaced
            from jax; a misconfigured coordinator should be loud).
    """
    coordinator = coordinator or os.environ.get("PHYRAX_JAX_COORDINATOR")
    if not coordinator:
        return False
    if num_processes is None:
        num_processes = int(os.environ.get("PHYRAX_JAX_NUM_PROCESSES", "0"))
    if not num_processes:
        raise ValueError(
            "PHYRAX_JAX_COORDINATOR is set but the world size is unknown: "
            "set PHYRAX_JAX_NUM_PROCESSES (and PHYRAX_JAX_PROCESS_ID) or "
            "pass num_processes/process_id explicitly")
    if process_id is None:
        process_id = int(os.environ.get("PHYRAX_JAX_PROCESS_ID", "0"))
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True
