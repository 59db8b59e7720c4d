"""Milliseconds per slot join in the host page cache: parking the
request's decode state (``page_put_us``) and reading it back for the
refill (``page_get_us``)."""
from per_join import per_join


def read(rec):
    us = per_join(rec, ("page_put_us", "page_get_us"))
    return None if us is None else us / 1e3
