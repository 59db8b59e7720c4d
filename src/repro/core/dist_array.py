"""Tiled distributed arrays with whole-array metadata (paper §4.1).

Phylanx: "Each of the tiles of the data arrays handled by a locality is
internally represented exactly like a fully local data array except that it
carries additional meta-information describing the whole (distributed)
array."  ``jax.Array`` + ``NamedSharding`` already is that representation;
``TiledArray`` adds the logical-dimension metadata (so re-tiling is a
declarative operation) and the paper's *overlapped tiling* (halo) support.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import collectives
from .sharding import ShardingRules, sharding_for, spec_for


@dataclasses.dataclass
class TiledArray:
    """A distributed array + the tiling plan that produced it."""

    data: jax.Array
    dims: tuple[str | None, ...]     # logical dim names, len == ndim
    mesh: Mesh
    rules: ShardingRules

    # -- construction -------------------------------------------------------
    @classmethod
    def tile(cls, x: jax.Array, dims: Sequence[str | None], mesh: Mesh,
             rules: ShardingRules) -> "TiledArray":
        sh = sharding_for(mesh, rules, x.shape, dims)
        return cls(jax.device_put(x, sh), tuple(dims), mesh, rules)

    # -- metadata ------------------------------------------------------------
    @property
    def spec(self) -> P:
        return spec_for(self.mesh, self.rules, self.data.shape, self.dims)

    @property
    def global_shape(self) -> tuple[int, ...]:
        return tuple(self.data.shape)

    def tile_shape(self) -> tuple[int, ...]:
        """Shape of the per-device tile."""
        sh = self.data.sharding.shard_shape(self.data.shape)
        return tuple(sh)

    # -- re-tiling (declarative redistribution) ------------------------------
    def retile(self, rules: ShardingRules) -> "TiledArray":
        sh = sharding_for(self.mesh, rules, self.data.shape, self.dims)
        return TiledArray(jax.device_put(self.data, sh), self.dims,
                          self.mesh, rules)

    def replicated(self) -> "TiledArray":
        sh = NamedSharding(self.mesh, P())
        return TiledArray(jax.device_put(self.data, sh), self.dims,
                          self.mesh, ShardingRules({}))

    # -- overlapped tiling ----------------------------------------------------
    def with_halo(self, dim_name: str, halo: int) -> jax.Array:
        """Return the array where each tile of ``dim_name`` is extended with
        ``halo`` ghost rows from its neighbours (spatial parallelism)."""
        axis = self.rules.axis_for(dim_name)
        if axis is None or (isinstance(axis, str) and axis not in self.mesh.shape):
            return self.data  # dimension not distributed: nothing to exchange
        assert isinstance(axis, str), "halo exchange over a single mesh axis"
        dim = self.dims.index(dim_name)
        in_spec = self.spec

        def body(x):
            return collectives.halo_exchange(x, axis, halo, dim=dim)

        out_parts = list(in_spec) + [None] * (len(self.dims) - len(in_spec))
        fn = jax.shard_map(body, mesh=self.mesh, in_specs=in_spec,
                              out_specs=P(*out_parts), check_vma=False)
        return fn(self.data)
