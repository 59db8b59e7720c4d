"""Device milliseconds a run of the decode program (``jit_decode_step``):
its device seconds in the traced window over its runs there."""


def read(rec):
    tr = rec["window"].trace
    mod = (tr or {}).get("modules", {}).get("jit_decode_step")
    return mod["seconds"] / mod["runs"] * 1e3 if mod and mod["runs"] else None
