"""Runtime-adaptive grain-size control (paper R4).

Phylanx adapts task grain size and message coalescing at runtime to maximise
utilisation.  On a TPU the knobs with the same effect are chosen per compile
from static shape/mesh arithmetic instead of per task at runtime:

  * gradient-fusion bucket bytes        (tensor fusion cap, R5)
  * microbatch count                    (pipeline / gradient accumulation)
  * remat (activation checkpoint) policy
  * flash-attention / kernel block shapes

``GrainPolicy.derive`` does the napkin math: it balances per-collective fixed
latency against the bandwidth cost of delaying overlap (bigger buckets start
later), and activation memory against recompute FLOPs.  Every decision is
returned with the numbers that produced it in ``rationale``, so a caller
can show *why* a grain was picked - the paper's "runtime-adaptive"
requirement made auditable.
"""
from __future__ import annotations

import dataclasses
from typing import Any

# TPU v5e model constants (per chip); the benchmark keeps its own peaks in
# bench/peaks.json.
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link
ICI_LINKS = 3                # usable links/chip in a 2/3-D torus
COLL_LATENCY = 5e-6          # per-collective launch+sync latency (s), per hop


@dataclasses.dataclass(frozen=True)
class GrainDecision:
    bucket_bytes: int
    n_microbatches: int
    remat: str                     # "none" | "block" | "full"
    attn_block_q: int
    attn_block_kv: int
    rationale: dict[str, Any]


class GrainPolicy:
    """Derive grain sizes from (model stats, mesh, shape) napkin math."""

    @staticmethod
    def bucket_bytes(total_grad_bytes: int, n_tensors: int, dp_degree: int,
                     backward_time_s: float) -> int:
        """Pick the fusion cap.

        Cost model for DP all-reduce of G bytes in k buckets overlapped with
        a backward pass of duration T:
          exposed = max(0, G*2(n-1)/n / BW_wire - T*(k-1)/k) + k * lat * hops
        Larger k hides more (first bucket launches earlier) but pays k
        latencies.  We approximate the optimum by matching per-bucket wire
        time to ~4x collective latency, clamped to [1 MiB, 64 MiB].
        """
        if dp_degree <= 1 or total_grad_bytes == 0:
            return max(total_grad_bytes, 1)
        wire_bw = ICI_BW * ICI_LINKS
        hops = dp_degree - 1
        target = 4.0 * COLL_LATENCY * hops * wire_bw / max(2 * (dp_degree - 1) / dp_degree, 1e-9)
        cap = int(min(max(target, 1 << 20), 64 << 20))
        # never fewer than 2 buckets if there is anything to overlap
        if total_grad_bytes > cap and total_grad_bytes // cap < 2:
            cap = total_grad_bytes // 2 + 1
        return cap

    @staticmethod
    def microbatches(global_batch: int, dp_degree: int, seq: int, d_model: int,
                     n_layers: int, hbm_bytes: float = 16e9,
                     per_act_bytes: int = 2) -> int:
        """Split the per-replica batch until checkpointed activations fit."""
        local_b = max(global_batch // max(dp_degree, 1), 1)
        act = local_b * seq * d_model * per_act_bytes * n_layers  # 1 residual/layer
        n = 1
        while act / n > 0.25 * hbm_bytes and n < local_b:
            n *= 2
        return min(n, local_b)

    @staticmethod
    def remat_policy(n_layers: int, d_model: int, seq: int, local_batch: int,
                     hbm_bytes: float = 16e9) -> str:
        full_acts = n_layers * local_batch * seq * d_model * 2 * 12  # ~12 tensors/block
        if full_acts < 0.3 * hbm_bytes:
            return "none"
        return "block"

    @staticmethod
    def attn_blocks(seq: int, head_dim: int) -> tuple[int, int]:
        """Flash-attention tile shapes: MXU-aligned, VMEM-bounded.

        VMEM ~= 64 MiB usable/2 for double buffering; working set per tile is
        (bq*d + bkv*d*2 + bq*bkv) * 4B.  128 alignment for the MXU.
        """
        bq = 128 if seq >= 128 else max(8, seq)
        bkv = 128
        while (bq * head_dim + 2 * bkv * head_dim + bq * bkv) * 4 < 8 << 20 and bkv < min(seq, 2048):
            bkv *= 2
        bkv = min(bkv, max(seq, 128))
        return bq, bkv

    @classmethod
    def derive(cls, *, n_params: int, n_tensors: int, global_batch: int,
               seq: int, d_model: int, n_layers: int, head_dim: int,
               dp_degree: int, grad_bytes_per_param: int = 2) -> GrainDecision:
        grad_bytes = n_params * grad_bytes_per_param
        # rough backward time: 4N*D flops (bwd ~2x fwd) at 40% MFU
        tokens = global_batch * seq
        bwd_t = 4 * n_params * tokens / max(dp_degree, 1) / (0.4 * PEAK_FLOPS)
        cap = cls.bucket_bytes(grad_bytes, n_tensors, dp_degree, bwd_t)
        micro = cls.microbatches(global_batch, dp_degree, seq, d_model, n_layers)
        remat = cls.remat_policy(n_layers, d_model, seq,
                                 max(global_batch // max(dp_degree, 1), 1))
        bq, bkv = cls.attn_blocks(seq, head_dim)
        return GrainDecision(
            bucket_bytes=cap, n_microbatches=micro, remat=remat,
            attn_block_q=bq, attn_block_kv=bkv,
            rationale={
                "grad_bytes": grad_bytes, "est_backward_s": bwd_t,
                "dp_degree": dp_degree, "n_tensors": n_tensors,
                "tokens": tokens,
            })
