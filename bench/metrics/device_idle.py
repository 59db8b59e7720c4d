"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""


def read(rec):
    tr = rec["window"].trace
    if not tr or not tr["devices"]:
        return None
    busy = sum(d["busy_s"] for d in tr["devices"].values()) \
        / len(tr["devices"])
    return 100.0 * (1.0 - busy / tr["window_s"])
