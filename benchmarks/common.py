"""Shared helpers for the benchmark harness."""
import os
import subprocess
import sys
import textwrap
import time

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_devices(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    """Run ``code`` in a child with ``n_devices`` host CPU devices (the
    child stays off any accelerator the parent may already hold)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    if p.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{p.stderr[-3000:]}")
    return p.stdout


def timeit(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median-ish wall time per call in seconds."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)
