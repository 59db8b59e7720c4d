"""Child process of ``bench_helpers.run_cell``:
``bench_drive.py <root> <fault> <run.py arguments>``.

Replaces the device check with a stand-in (one TPU v5e per CPU device),
plants ``fault`` (``bench/faults.py``; empty for none) in the program under
test, then runs ``run.main``.
"""
import pathlib
import sys

root = pathlib.Path(sys.argv[1])
fault = sys.argv[2]
sys.path[:0] = [str(root / "bench"), str(root / "src")]

import common  # noqa: E402
import faults  # noqa: E402
import run  # noqa: E402

common.require_device = lambda chips, peaks: {
    "platform": "cpu", "kind": "TPU v5 lite", "count": chips}
faults.plant(fault)
sys.exit(run.main(sys.argv[3:], root=root))
