"""Divisibility-aware sharding-rules engine ("tiling plans").

Phylanx represents a distributed array as local tiles plus meta-information
describing the whole array. The JAX-native equivalent is a
``NamedSharding(mesh, PartitionSpec)``; what JAX does *not* give us is a
declarative mapping from *logical dimension names* (``"batch"``, ``"heads"``,
``"d_ff"``, ...) to mesh axes with graceful fallback when a dimension does not
divide the axis.  This module provides that: models annotate every parameter
and activation with logical dim names and the engine turns them into concrete
``PartitionSpec``s, replicating any dimension that cannot be tiled evenly
(e.g. 2 KV heads under 16-way tensor parallelism).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical dimension vocabulary (shared by models / steps / dryrun)
# ---------------------------------------------------------------------------
#   batch      -> data-parallel axes ("pod","data")
#   seq        -> sequence; sharded only under sequence parallelism
#   model-ish  -> "model" axis: heads, kv_heads, d_ff, vocab, experts, inner
#   replicated -> None
DEFAULT_RULES: dict[str, tuple[str, ...] | str | None] = {
    "batch": ("pod", "data"),
    "seq": None,              # flipped to "model" under sequence parallelism
    "kv_seq": None,           # long-context KV sharding -> "data"
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "d_ff": "model",
    "vocab": "model",
    "experts": "model",
    "expert_cap": None,
    "inner": "model",         # mamba2 / mlstm inner channels
    "state": None,            # SSM state dim
    "conv": None,
    "layers": None,           # scan-over-layers stacking dim
    "stage": None,            # pipeline stage dim (PP experiments)
    "channels": "model",      # CNN channels
    "spatial": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """A tiling plan: logical dim name -> mesh axis (or tuple of axes)."""

    rules: Mapping[str, tuple[str, ...] | str | None]

    def with_overrides(self, **ov) -> "ShardingRules":
        new = dict(self.rules)
        new.update(ov)
        return ShardingRules(new)

    def axis_for(self, dim: str) -> tuple[str, ...] | str | None:
        return self.rules.get(dim, None)


def default_rules(*, sequence_parallel: bool = False,
                  long_context_kv: bool = False) -> ShardingRules:
    r = dict(DEFAULT_RULES)
    if sequence_parallel:
        r["seq"] = "model"
    if long_context_kv:
        r["kv_seq"] = "data"
    return ShardingRules(r)


def _axis_size(mesh: Mesh, axis: tuple[str, ...] | str | None) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        axis = (axis,)
    size = 1
    for a in axis:
        if a in mesh.shape:
            size *= mesh.shape[a]
    return size


def _present(mesh: Mesh, axis: tuple[str, ...] | str | None):
    """Filter an axis assignment down to axes present in this mesh."""
    if axis is None:
        return None
    if isinstance(axis, str):
        return axis if axis in mesh.shape else None
    axes = tuple(a for a in axis if a in mesh.shape)
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def spec_for(mesh: Mesh, rules: ShardingRules, shape: Sequence[int],
             dims: Sequence[str | None]) -> P:
    """PartitionSpec for a concrete shape with divisibility fallback.

    A dim is sharded on its mapped mesh axes only when evenly divisible;
    otherwise it is replicated.  Axes may be consumed at most once per spec
    (XLA requirement) - first dim wins.
    """
    assert len(shape) == len(dims), (shape, dims)
    used: set[str] = set()
    parts: list[Any] = []
    for size, dim in zip(shape, dims):
        axis = _present(mesh, rules.axis_for(dim)) if dim is not None else None
        if axis is None:
            parts.append(None)
            continue
        ax_tuple = (axis,) if isinstance(axis, str) else tuple(axis)
        if any(a in used for a in ax_tuple):
            parts.append(None)
            continue
        asize = _axis_size(mesh, ax_tuple)
        if asize <= 1 or size % asize != 0:
            parts.append(None)
            continue
        used.update(ax_tuple)
        parts.append(ax_tuple if len(ax_tuple) > 1 else ax_tuple[0])
    # strip trailing Nones for tidiness
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def sharding_for(mesh: Mesh, rules: ShardingRules, shape: Sequence[int],
                 dims: Sequence[str | None]) -> NamedSharding:
    return NamedSharding(mesh, spec_for(mesh, rules, shape, dims))


# ---------------------------------------------------------------------------
# Parameter specs: shape + logical dims + init
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    dims: tuple[str | None, ...]
    dtype: Any = jnp.float32
    init: str = "normal"      # normal | zeros | ones | scaled
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.dims), (self.shape, self.dims)

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) if self.shape else 1

    def struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)


def initialize(spec: ParamSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "normal":
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)
        std = spec.scale / math.sqrt(max(fan_in, 1))
        return (std * jax.random.normal(key, spec.shape)).astype(spec.dtype)
    if spec.init == "scaled":
        return (spec.scale * jax.random.normal(key, spec.shape)).astype(spec.dtype)
    raise ValueError(f"unknown init {spec.init}")


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init_leaf(spec: ParamSpec, sharding, key: jax.Array) -> jax.Array:
    # one program per (spec, placement): the draw, scale and cast fuse, so
    # a bf16 leaf never holds a float32 copy of itself on the device
    x = initialize(spec, key)
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def init_params(specs, key: jax.Array, shardings=None):
    """Initialize a pytree of ParamSpec into a pytree of arrays, each leaf
    born where ``shardings`` (a matching tree of ``NamedSharding``; the
    default device when None) places it."""
    is_spec = lambda x: isinstance(x, ParamSpec)
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    placed = ([None] * len(leaves) if shardings is None
              else treedef.flatten_up_to(shardings))
    keys = jax.random.split(key, len(leaves))
    arrs = [_init_leaf(s, sh, k) for s, sh, k in zip(leaves, placed, keys)]
    return jax.tree.unflatten(treedef, arrs)


def param_structs(specs):
    return jax.tree.map(lambda s: s.struct(), specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_shardings(specs, mesh: Mesh, rules: ShardingRules):
    return jax.tree.map(
        lambda s: sharding_for(mesh, rules, s.shape, s.dims), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_count(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return sum(s.size for s in leaves)


# ---------------------------------------------------------------------------
# Activation constraint hook (sequence parallelism and friends)
# ---------------------------------------------------------------------------
# Installed by a step function for the duration of its trace; model code
# calls ``act_constrain(x, dims)`` between blocks.  Outside such a trace it
# is a no-op, so models stay mesh-agnostic (R8) and never see the mesh of a
# step traced earlier.
_ACT_HOOK: list = [None]


@contextlib.contextmanager
def act_hook(mesh: Mesh, rules: ShardingRules):
    """Constrain activations by ``rules`` on ``mesh`` inside the block."""
    prev = _ACT_HOOK[0]
    _ACT_HOOK[0] = (mesh, rules)
    try:
        yield
    finally:
        _ACT_HOOK[0] = prev


def act_constrain(x: jax.Array, dims: Sequence[str | None]) -> jax.Array:
    hook = _ACT_HOOK[0]
    if hook is None or len(dims) != x.ndim:
        return x
    mesh, rules = hook
    spec = spec_for(mesh, rules, x.shape, dims)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
