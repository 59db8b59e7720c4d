"""The whole step's share of the chips' peak: the least time the window's
work needs at the roofline (per kind of step, the larger of its flops over
peak flop/s and its bytes over peak bytes/s; bench/flops.py counts both
from the shapes), over the window."""


def read(rec):
    t = rec.get("roofline_s")
    return 100.0 * t / rec["window"].seconds if t else None
