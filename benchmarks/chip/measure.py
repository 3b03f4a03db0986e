"""Helpers for the measured window: the traced span, the count of
compiles inside the window, Python's garbage collector around it, and the
peak device memory."""
from __future__ import annotations

import gc
import shutil
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TRACE_DIR = ROOT / ".bench_traces"


def memory_peak(n: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:n]:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Profiler:
    """The traced run: ``jax.profiler`` on for the first ``span_s``
    seconds of the window (a few seconds of trace; a whole window's is
    too large to read back), with the ``bench.window`` annotation over
    exactly that span."""

    def __init__(self, on: bool, span_s: float, out: Path):
        self.on, self.span_s, self.out = on, span_s, out
        self.lo = self.hi = None
        self._ann = None

    def open(self):
        if not self.on or self.lo is not None:
            return
        import jax

        shutil.rmtree(self.out, ignore_errors=True)
        jax.profiler.start_trace(str(self.out))
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.lo = time.perf_counter()

    def maybe_close(self, t: float):
        if self._ann is not None and t - self.lo >= self.span_s:
            self.close()

    def close(self):
        if self._ann is None:
            return
        import jax

        self.hi = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()


def compile_counter():
    """Counts backend compiles from now on (the window must have none)."""
    import jax

    box = {"n": 0, "on": False}

    def listen(event, duration, **kw):
        if box["on"] and event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return box


class GcWatch:
    """Python's garbage collector around the window. Set-up's garbage is
    collected and what survives is frozen before the window opens, so
    that no full collection inside the window walks the objects that
    building the program left behind; the collections that do run in the
    window are timed."""

    def __init__(self):
        self.collect_s, self.frozen, self.runs = 0.0, 0, []
        self._t = None

    def open(self):
        t = time.perf_counter()
        gc.collect()
        self.collect_s = time.perf_counter() - t
        gc.freeze()
        self.frozen = gc.get_freeze_count()
        gc.callbacks.append(self._time)

    def _time(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.runs.append((time.perf_counter() - self._t,
                              info["generation"]))
            self._t = None

    def close(self):
        gc.callbacks.remove(self._time)
        gc.unfreeze()

    def summary(self) -> str:
        worst = max(self.runs, default=(0.0, None))
        return (f"garbage collection: {self.collect_s:.3f} s over "
                f"{self.frozen} objects before the window; in it "
                f"{len(self.runs)} collections, the longest "
                f"{1e3 * worst[0]:.2f} ms (generation {worst[1]})")
