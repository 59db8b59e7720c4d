"""Paper Figure 1: Phylanx vs Horovod on the 4-layer HAR CNN.

Two parts:
  1. MEASURED - the full training step (fwd+bwd+solver+collectives) for both
     strategies on 1/2/4/8 local host devices, same global minibatch -
     reproducing the comparison *inside one system*.  The paper's claim is
     that the fused-async strategy keeps scaling where per-tensor blocking
     all-reduce flattens.
  2. MODELLED - an alpha-beta projection to 128 nodes driven by the measured
     per-strategy collective inventory (launch count, bytes from the fusion
     plan), with paper-era CPU-cluster constants: alpha=50us per collective
     hop, beta=125 MB/s effective per node (Horovod's Gloo TCP backend),
     0.5 effective TFLOP/s per 48-core Xeon node.  CSV columns report both.
"""
from __future__ import annotations

import json

import numpy as np

from .common import emit, run_devices

ALPHA = 50e-6          # per-collective latency (CPU cluster, gigabit-era)
# effective per-node all-reduce bandwidth: the paper runs Horovod with the
# Gloo TCP backend on a CPU cluster - gigabit-era effective throughput
BETA = 125e6
NODE_FLOPS = 0.5e12    # effective fp32 throughput of a 48-core Xeon node
MB = 8000              # the paper's minibatch
# analytic fwd+bwd FLOPs per HAR sample for the width-64 CNN (conv GEMMs)
FLOPS_PER_SAMPLE = 36e6


_MEASURE = """
import json, time
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import fusion, overlap
from repro.core.sharding import init_params
from repro.data.pipeline import HARStream
from repro.models import cnn
from repro.optim import optimizers as optim
from repro.optim.optimizers import OptConfig

n = {n}
strategy = "{strategy}"
from repro.launch.mesh import make_mesh
mesh = make_mesh((n,), ("data",))
oc = OptConfig(kind="sgd", lr=1e-2, grad_clip=1e9)
specs = cnn.har_cnn_specs(width=64)
params = init_params(specs, jax.random.PRNGKey(0))
batch = HARStream(batch={mb}).batch_at(0)

def body(params, x, y):
    loss, grads = jax.value_and_grad(cnn.har_cnn_loss)(params,
                                                       {{"x": x, "y": y}})
    if strategy == "horovod":
        grads = overlap.exchange_horovod(grads, ("data",))
    else:
        grads = overlap.exchange_phylanx(grads, ("data",), 1 << 20)
    params, _, _ = optim.update(grads, {{"count": jnp.zeros((), jnp.int32)}},
                                params, oc)
    return loss, params

from jax import shard_map
fn = jax.jit(shard_map(body, mesh=mesh,
                           in_specs=(P(), P("data"), P("data")),
                           out_specs=(P(), P()), axis_names={{"data"}},
                           check_vma=False))
x = jax.device_put(batch["x"], NamedSharding(mesh, P("data")))
y = jax.device_put(batch["y"], NamedSharding(mesh, P("data")))
loss, p2 = fn(params, x, y)
jax.block_until_ready(p2)
t0 = time.perf_counter()
for _ in range(5):
    loss, p2 = fn(params, x, y)
jax.block_until_ready(p2)
dt = (time.perf_counter() - t0) / 5
print("RESULT", json.dumps({{"dt": dt}}))
"""


def measured(mb: int = 2048):
    out = {}
    for strategy in ("phylanx", "horovod"):
        for n in (1, 2, 4, 8):
            r = run_devices(_MEASURE.format(n=n, strategy=strategy, mb=mb),
                            n_devices=n)
            dt = json.loads(r.split("RESULT", 1)[1])["dt"]
            out[(strategy, n)] = dt
            emit(f"fig1_measured_{strategy}_n{n}", dt * 1e6,
                 f"mb={mb};full_step")
    return out


def modelled(t1: float, mb: int):
    """alpha-beta projection of the paper's 1..128-node experiment."""
    from repro.core import fusion
    from repro.models import cnn
    specs = cnn.har_cnn_specs(width=64)
    import jax
    structs = jax.tree.map(lambda s: s.struct(), specs,
                           is_leaf=lambda x: hasattr(x, "dims"))
    leaves = jax.tree.leaves(structs)
    n_tensors = len(leaves)
    grad_bytes = sum(int(np.prod(l.shape)) * 4 for l in leaves)
    plan = fusion.make_plan(structs, cap_bytes=1 << 20)
    rows = {}
    for strategy, k_coll in (("phylanx", plan.n_buckets),
                             ("horovod", n_tensors)):
        for nodes in (1, 2, 4, 8, 16, 32, 64, 128):
            compute = MB * FLOPS_PER_SAMPLE / NODE_FLOPS / nodes
            wire = 2 * grad_bytes * (nodes - 1) / nodes / BETA
            lat = k_coll * ALPHA * (1 if strategy == "phylanx" else 2)
            # horovod (per-tensor, sequential): latency and wire are exposed;
            # phylanx (fused, async): overlap hides up to 60% of wire
            if strategy == "phylanx" and nodes > 1:
                comm = lat + 0.4 * wire
            elif nodes > 1:
                comm = lat + wire
            else:
                comm = 0.0
            t = compute + comm
            rows[(strategy, nodes)] = t
            emit(f"fig1_model_{strategy}_n{nodes}", t * 1e6,
                 f"mb={MB};alpha_beta_model")
    # the paper's headline: phylanx faster by >=18% at >=32 nodes
    for nodes in (32, 64, 128):
        gain = (rows[("horovod", nodes)] - rows[("phylanx", nodes)]) \
            / rows[("horovod", nodes)]
        emit(f"fig1_gain_n{nodes}", gain * 1e6, f"relative_gain={gain:.2%}")
    return rows


mb_measured = 2048


def main():
    res = measured(mb_measured)
    t1 = res[("phylanx", 1)]
    modelled(t1, mb_measured)


if __name__ == "__main__":
    main()
