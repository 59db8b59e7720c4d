"""Gap between consecutive tokens of one request at the client, p95 over
every gap that ends in the window."""
from common import percentile


def read(rec):
    return percentile(rec.get("itl_s"), 95, 1e3)
