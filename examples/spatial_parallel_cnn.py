"""Spatial parallelism with overlapped tiling (paper §4.1: "Phylanx supports
overlapped tiling, which is beneficial in spatial parallelization. A halo
exchange is needed in forward and backward pass").

The HAR CNN's time axis is sharded across 4 devices; each shard holds its
tile plus halo ghost rows exchanged via collective_permute, so a k=3 VALID
conv over the halo-extended tiles equals the unsharded conv exactly.

    PYTHONPATH=src python examples/spatial_parallel_cnn.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import collectives  # noqa: E402
from repro.core.sharding import init_params  # noqa: E402
from repro.models import cnn  # noqa: E402


def main():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    params = init_params(cnn.har_cnn_specs(), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 9))

    # --- unsharded reference: one conv over the full window ---------------
    ref = cnn._conv1d(x, params["conv1"]["w"], params["conv1"]["b"])

    # --- spatially sharded: tile the time axis, exchange k-1 halo rows ----
    halo = 1  # (k - 1) // 2 for k=3

    def sharded_conv(x_tile, w, b):
        xt = collectives.halo_exchange(x_tile, "data", halo, dim=1)
        y = cnn._conv1d(xt, w, b)
        return y  # [B, tile, Cout] after VALID conv over the halo'd tile

    from jax import shard_map
    fn = jax.jit(shard_map(
        lambda x, w, b: sharded_conv(x, w, b), mesh=mesh,
        in_specs=(P(None, "data"), P(), P()),
        out_specs=P(None, "data"), check_vma=False))
    xs = jax.device_put(x, NamedSharding(mesh, P(None, "data")))
    y = fn(xs, params["conv1"]["w"], params["conv1"]["b"])

    # interior rows must match exactly (edge tiles see zero-padded ghosts,
    # so compare the valid interior of each tile)
    y_np, ref_np = np.asarray(y), np.asarray(ref)
    tile = 128 // 4
    max_err = 0.0
    for s in range(4):
        # tile s's outputs cover global rows [s*tile - halo, ...] except at
        # the edges; compare the overlap with the reference
        for j in range(tile):
            g = s * tile - halo + j      # global output row index
            if 0 <= g < ref_np.shape[1]:
                max_err = max(max_err, float(
                    np.abs(y_np[:, s * tile + j] - ref_np[:, g]).max()))
    print(f"spatial-parallel conv vs unsharded: max_err={max_err:.2e}")
    assert max_err < 1e-5
    print("overlapped tiling (halo exchange) reproduces the unsharded conv")


if __name__ == "__main__":
    main()
