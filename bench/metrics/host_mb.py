"""Megabytes of decode state per slot join that cross between host and
device: pulled to the host after the prefill (``d2h_bytes``) and put back
by the refill (``h2d_bytes``)."""
from per_join import per_join


def read(rec):
    b = per_join(rec, ("d2h_bytes", "h2d_bytes"))
    return None if b is None else b / 1e6
