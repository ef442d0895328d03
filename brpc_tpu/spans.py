"""Spans inside the program's Python side: always on, bounded, two clocks.

`span(name, request=...)` is a context manager that

  (a) always appends `(name, start, end, request, thread)` on
      `time.monotonic()` to a bounded in-memory ring (`CAPACITY` records,
      oldest dropped), so a reader in the same process can cut any recent
      interval out of it afterwards (`snapshot`) and reduce it to per-name
      self times (`self_times`: a span's duration minus the spans nested
      inside it -- what the span itself cost, never counted twice);
  (b) opens `jax.profiler.TraceAnnotation("tpurpc:" + name)`, which costs
      one atomic check while no profiler session runs and puts the span on
      the device trace's clock, on the host plane, while one does.

There is no switch: like the C++ stage clock (cpp/tvar/stage_recorder.h)
it is part of the program, and what it costs is part of what is measured.
The staging-ring pass (`device_path._ChunkPipeline`) is its first user;
`benchmark/layer_metrics/ring_*_share.py` read it.
"""
import threading
import time
from collections import deque

CAPACITY = 1 << 17  # records kept; about 13 s of the ring pass at 1 MiB chunks
PREFIX = "tpurpc:"

_ring = deque(maxlen=CAPACITY)
_now = time.monotonic
_ident = threading.get_ident
_annotation = None  # resolved at the first span: jax's TraceAnnotation


def _resolve_annotation():
    """jax is imported at the first span, not with this module."""
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    return _annotation


class span:
    """`with span("ring.stage", request=(pass_no, k)):`

    `request` says whose work this is (any small hashable). Nesting is not
    recorded: `self_times` recovers it from the times, per thread."""

    __slots__ = ("name", "request", "_t0", "_ann")

    def __init__(self, name, request=None):
        self.name = name
        self.request = request

    def __enter__(self):
        self._ann = (_annotation or _resolve_annotation())(PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = _now()
        self._ann.__exit__(exc_type, exc, tb)
        _ring.append((self.name, self._t0, t1, self.request, _ident()))
        return False


def record(name, start, end, request=None):
    """A span that no one thread holds: it began at `start` on one thread
    and ends now-ish, at `end`, on another (a call's wait for the last of
    its chunks). Kept whole, on a line of its own where a thread's id
    stands, so `self_times` gives it its full duration and takes nothing
    from the spans of the thread that records it. Not on the profiler's
    clock."""
    _ring.append((name, start, end, request, (name, start)))


def snapshot(since=None, until=None):
    """The ring's records that touch [since, until], each clipped to it
    (times are `time.monotonic()`; None = unbounded), oldest first."""
    out = []
    for rec in list(_ring):
        start, end = rec[1], rec[2]
        if since is not None:
            if end <= since:
                continue
            start = max(start, since)
        if until is not None:
            if start >= until:
                continue
            end = min(end, until)
        out.append((rec[0], start, end) + rec[3:])
    return out


def self_times(records):
    """{name: seconds} of self time over `records` (as `snapshot` gives
    them): each span's duration minus the durations of the spans directly
    nested inside it on the same thread. The values add up to the time
    covered by the outermost spans, exactly, whatever the nesting."""
    totals = {}
    by_thread = {}
    for rec in records:
        by_thread.setdefault(rec[4], []).append(rec)
    for recs in by_thread.values():
        recs.sort(key=lambda r: (r[1], -r[2]))
        stack = []  # open spans: [name, end, self seconds so far]
        for name, start, end, *_ in recs:
            while stack and stack[-1][1] <= start:
                done = stack.pop()
                totals[done[0]] = totals.get(done[0], 0.0) + done[2]
            if stack:
                end = min(end, stack[-1][1])  # a child never outlasts its parent
                stack[-1][2] -= end - start
            stack.append([name, end, end - start])
        for done in stack:
            totals[done[0]] = totals.get(done[0], 0.0) + done[2]
    return totals


def clear():
    """Empty the ring (tests)."""
    _ring.clear()
