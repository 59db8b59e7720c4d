"""Milliseconds per decode round: the window over the rounds decoded in it.
Each round emits one token per slot, live or padded, so the rounds are the
gateway's (real + padded) slot-token counters over the slots."""


def read(rec):
    win = rec["window"]
    slots = rec["cell"].traffic["slots"]
    rounds = (win.delta("real_tokens") + win.delta("padded_slot_tokens")) \
        / slots
    return win.seconds * 1e3 / rounds if rounds else None
