"""Tokens trained per second: the steps dispatched in the window, each of
the traffic's batch x sequence tokens, over the window, which closes once
the last of them is done (host clock)."""


def read(rec):
    steps = rec.get("steps")
    return steps * rec["tokens_per_step"] / rec["window"].seconds \
        if steps else None
