"""The whole step's share of the chips' peak: the least time the window's
work needs at the roofline, over the window.  The cell's runner gives that
time (``roofline_s``): for serving, per kind of step the larger of its
flops over peak flop/s and its bytes over peak bytes/s; for training, the
flops over peak flop/s (``bench/flops.py`` counts both from the shapes)."""


def read(rec):
    t = rec.get("roofline_s")
    return 100.0 * t / rec["window"].seconds if t else None
