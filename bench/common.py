"""What every cell shares: the device check, the compile clock, host spans
and the measured window."""
from __future__ import annotations

import contextlib
import json
import pathlib
import shutil
import sys
import tempfile
import time

import jax

def log(msg: str):
    """An earlier output line: on stderr, so stdout's last line stays the
    result."""
    print(msg, file=sys.stderr, flush=True)


def load_peaks(root: pathlib.Path) -> dict:
    return json.loads((root / "bench" / "peaks.json").read_text())


def require_device(chips: int, peaks: dict) -> dict:
    """The device JAX found, or SystemExit when it is not a TPU, its kind
    is not in the peaks table, or there are fewer chips than the cell
    asks for."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform "
                         f"{d.platform!r}); nothing was run")
    if d.device_kind not in peaks:
        raise SystemExit(f"bench: device kind {d.device_kind!r} is not in "
                         f"bench/peaks.json; nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}; nothing was run")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


class CompileClock:
    """Backend compiles and persistent-cache hits, from ``jax.monitoring``
    events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def host_span(name: str):
    """A host span in the profiler's trace (a no-op while not tracing)."""
    return jax.profiler.TraceAnnotation(name)


class Window:
    """The measured window: host-clock bounds, program counters at both
    ends, compiles inside it, and the reduced device trace."""

    def __init__(self):
        self.t_open = self.t_close = 0.0
        self.counters_open: dict = {}
        self.counters_close: dict = {}
        self.compiles = 0
        self.trace: dict | None = None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def delta(self, key: str) -> int:
        return (self.counters_close.get(key, 0)
                - self.counters_open.get(key, 0))


@contextlib.contextmanager
def compile_free_window(clock: CompileClock, trace: bool, counters=None):
    """Open the window on entry, close it on exit.  With ``trace`` the
    profiler records it (started before the window opens, stopped after it
    closes) and the trace is reduced and deleted."""
    from trace_reduce import reduce_trace
    win = Window()
    tdir = None
    span = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 2
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        span = host_span("bench.window")
        span.__enter__()
    c0 = clock.compiles
    win.counters_open = counters() if counters else {}
    win.t_open = time.perf_counter()
    try:
        yield win
    finally:
        win.t_close = time.perf_counter()
        win.counters_close = counters() if counters else {}
        win.compiles = clock.compiles - c0
        if trace:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            try:
                win.trace = reduce_trace(tdir)
            finally:
                shutil.rmtree(tdir, ignore_errors=True)


def memory_peak(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def live_bytes() -> int:
    return sum(a.nbytes for a in jax.live_arrays())


def percentile(values, p: float, scale: float = 1.0):
    """Nearest-rank percentile (the smallest value with at least p% of the
    values at or below it), times ``scale``; None for no values."""
    if not values:
        return None
    s = sorted(values)
    k = max(0, min(len(s) - 1, -(-len(s) * p // 100) - 1))
    return s[int(k)] * scale
