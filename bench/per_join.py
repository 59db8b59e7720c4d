"""Program counters per slot join: what the readers of the join and paging
metrics share."""


def per_join(rec, keys) -> float | None:
    """The window's growth of the program counters ``keys``, summed, over
    the slot joins in the window (``refills``).  None where the program
    keeps one of them under no such name, or no request joined."""
    win = rec["window"]
    if not all(k in win.counters_close for k in keys):
        return None
    joins = win.delta("refills")
    return sum(win.delta(k) for k in keys) / joins if joins else None
