"""Device milliseconds a step in the MoE layers: the ops under any
``moe.*`` named scope (routing, dispatch, the held experts, the shared
experts, the combine; ``bench/scopes.py``), averaged over the cell's
chips."""


def read(rec):
    tr = rec["window"].trace
    s = (tr or {}).get("scopes", {}).get("moe")
    steps = rec.get("steps")
    return s / steps * 1e3 if s and steps else None
