"""Child process of ``test_bench_moonlight.run_moe_cell``:
``moe_drive.py <root> <fault> <run.py arguments>``.

``bench_drive.py`` with the fault taken from ``bench/moe_faults.py``
(empty for none).
"""
import pathlib
import sys

root = pathlib.Path(sys.argv[1])
fault = sys.argv[2]
sys.path[:0] = [str(root / "bench"), str(root / "src")]

import common  # noqa: E402
import moe_faults  # noqa: E402
import run  # noqa: E402

common.require_device = lambda chips, peaks: {
    "platform": "cpu", "kind": "TPU v5 lite", "count": chips}
moe_faults.plant(fault)
sys.exit(run.main(sys.argv[3:], root=root))
