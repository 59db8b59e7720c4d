"""Milliseconds per slot join that the decode chain waits on: the
gateway's round loop blocked on the request's prefill (``join_wait_us``)
plus the refill node that pages its state in and scatters it into the
batch (``refill_us``)."""
from per_join import per_join


def read(rec):
    us = per_join(rec, ("join_wait_us", "refill_us"))
    return None if us is None else us / 1e3
