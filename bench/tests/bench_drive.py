"""Child process of ``bench_helpers.run_cell``:
``bench_drive.py <root> <fault> <run.py arguments>``.

Replaces the device check with a stand-in (one TPU v5e per CPU device) and
plants ``fault`` in the program under test, then runs ``run.main``:

  token       the gateway's decode step returns every token plus one.
"""
import pathlib
import sys

root = pathlib.Path(sys.argv[1])
fault = sys.argv[2]
sys.path[:0] = [str(root / "bench"), str(root / "src")]

import common  # noqa: E402
import run  # noqa: E402

common.require_device = lambda chips, peaks: {
    "platform": "cpu", "kind": "TPU v5 lite", "count": chips}

if fault == "token":
    from repro.frontend import gateway

    real_decode = gateway.Gateway._decode_fn

    def bad_decode(self, carry, pos):
        tok, cache = real_decode(self, carry, pos)
        return (tok + 1) % self.dec.model.cfg.vocab, cache
    gateway.Gateway._decode_fn = bad_decode
elif fault:
    raise SystemExit(f"unknown fault {fault!r}")

sys.exit(run.main(sys.argv[3:], root=root))
