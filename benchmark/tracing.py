"""The traced window: one profiler session around the measured window.

A traced run measures a window of its own, `TRACE_WINDOW_S` at most (a
trace of the full `--seconds` would be large and would slow the host);
its end-to-end numbers are never reported, its per-layer numbers are.
Only the process that holds the chip can trace it, so this lives in
`run.py`'s process.
"""
import shutil
import tempfile
import time
from contextlib import nullcontext

from benchmark import xplane

TRACE_WINDOW_S = 3.0


class TraceWindow:
    """`with TraceWindow(on) as tw:` around the measured window; afterwards
    `tw.summary()` is the reduction of what the profiler saw (None when
    tracing is off)."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._dir = None
        self._span = None
        self.t0 = self.t1 = None

    def __enter__(self):
        if self.enabled:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # spans only: the host loop is timed
            self._dir = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
            self._span.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        if self.enabled:
            import jax

            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return False

    def span(self, name: str):
        """A host span on the trace's clock (free when tracing is off)."""
        if not self.enabled:
            return nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(xplane.SPAN_PREFIX + name)

    def summary(self):
        if not self.enabled:
            return None
        try:
            events = xplane.load_events(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return xplane.reduce_events(events, window_s=self.t1 - self.t0)


def window_seconds(seconds: float, trace: bool) -> float:
    return min(seconds, TRACE_WINDOW_S) if trace else seconds
