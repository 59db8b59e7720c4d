"""The held experts' grouped matmuls against the chip's bf16 peak: their
flops (forward and backward, from the window's ``moe_assigned``; the
reference's ``expert_flops``) at the peak, over the device time of the ops
under ``moe.experts`` (``bench/scopes.py``), %.  Recomputation under remat
is in the time and not in the flops."""


def read(rec):
    tr = rec["window"].trace
    s = (tr or {}).get("scopes", {}).get("moe.experts")
    t = rec.get("experts_roofline_s")
    return 100.0 * t / s if s and t else None
