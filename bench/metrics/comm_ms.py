"""Device milliseconds a step in collective operations (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all, by op name in
``bench/trace_reduce.py``), averaged over the cell's chips."""


def read(rec):
    tr = rec["window"].trace
    steps = rec.get("steps")
    if not tr or not tr["devices"] or not steps:
        return None
    comm = sum(d["comm_s"] for d in tr["devices"].values()) \
        / len(tr["devices"])
    return comm / steps * 1e3 if comm else None
