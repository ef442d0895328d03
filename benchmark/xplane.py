"""From a profiler trace to device busy time, op times and idle gaps.

`load_events` reads the `.xplane.pb` the JAX profiler wrote (with nothing
but JAX); everything after it works on plain tuples, so the tests drive it
with hand-made event lists. Times are seconds on the trace's own clock.

An event is `(plane, line, name, start_s, dur_s)`. Device work is what the
trace puts on a device plane's op line; host spans are the harness's own
`jax.profiler.TraceAnnotation`s, whose names start with `bench:`.
"""
import glob
import os
import re
from bisect import bisect_right
from collections import defaultdict

DEVICE_PLANE_PREFIX = "/device:"
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


_HLO_NAME = re.compile(r"^%([\w.\-]+) = ")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


def short_name(name: str) -> str:
    """The trace names a device op by its whole HLO line (`%fusion.1 =
    u32[...] fusion(...)`); the op's own name is what stays the same from
    run to run and fits a ledger."""
    m = _HLO_NAME.match(name)
    return m.group(1) if m else name


def load_events(trace_dir: str) -> list:
    """Every event of the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(files[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                events.append((plane.name, line.name, short_name(ev.name),
                               ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return events


def merge(intervals) -> list:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def clip(merged, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in merged
            if min(e, hi) > max(s, lo)]


def complement(merged, lo: float, hi: float) -> list:
    """The gaps of `merged` inside [lo, hi]."""
    out, at = [], lo
    for s, e in clip(merged, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a_merged, b_merged) -> float:
    """Seconds covered by both unions."""
    i = j = 0
    acc = 0.0
    while i < len(a_merged) and j < len(b_merged):
        s = max(a_merged[i][0], b_merged[j][0])
        e = min(a_merged[i][1], b_merged[j][1])
        if e > s:
            acc += e - s
        if a_merged[i][1] < b_merged[j][1]:
            i += 1
        else:
            j += 1
    return acc


def reduce_events(events, window_s: float | None = None) -> dict:
    """The trace's summary.

    window: the `bench:window` span where the trace has one, else the
    extent of all events; its length is `window_s` if given (the host
    clock's reading of the same window) else the trace's own.
    Returns {"window_s", "chips": {plane: {"busy_s", "ops": {name: [seconds,
    count]}, "modules": {name: [seconds, count]}, "module_ops": {module:
    {op: [seconds, count]}}}}, "busy_s" (mean over the chips that ran
    anything), "idle_gaps": {span name: idle seconds of the busiest chip
    under that span}}. A module is named without its program id; an op
    belongs to the module whose interval holds its start."""
    spans = defaultdict(list)
    ops = defaultdict(list)      # plane -> [(name, s, e)]
    modules = defaultdict(list)
    lo, hi = float("inf"), float("-inf")
    for plane, line, name, start, dur in events:
        lo, hi = min(lo, start), max(hi, start + dur)
        if plane.startswith(DEVICE_PLANE_PREFIX):
            if line in OP_LINES:
                ops[plane].append((name, start, start + dur))
            elif line in MODULE_LINES:
                modules[plane].append((_PROGRAM_ID.sub("", name), start,
                                       start + dur))
        elif name.startswith(SPAN_PREFIX):
            spans[name].append((start, start + dur))
    if spans.get(WINDOW_SPAN):
        lo, hi = spans[WINDOW_SPAN][0]
    if not events:
        lo = hi = 0.0
    def totals(items):
        """{name: [seconds inside the window, count]}"""
        acc = defaultdict(lambda: [0.0, 0])
        for name, s, e in items:
            if e > lo and s < hi:
                acc[name][0] += min(e, hi) - max(s, lo)
                acc[name][1] += 1
        return dict(acc)

    def by_module(mods, items):
        """{module: {op: [seconds, count]}}, ops outside every module left
        out."""
        mods = sorted(mods, key=lambda m: m[1])
        starts = [m[1] for m in mods]
        inside = defaultdict(list)
        for name, s, e in items:
            i = bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][2]:
                inside[mods[i][0]].append((name, s, e))
        return {mod: totals(its) for mod, its in inside.items()}

    chips = {}
    for plane in sorted(set(ops) | set(modules)):
        busy = clip(merge((s, e) for _, s, e in ops[plane]), lo, hi)
        chips[plane] = {"busy_s": total(busy), "busy": busy,
                        "ops": totals(ops[plane]),
                        "modules": totals(modules[plane]),
                        "module_ops": by_module(modules[plane], ops[plane])}
    ran = [c for c in chips.values() if c["busy_s"] > 0]
    out = {"window_s": window_s if window_s is not None else hi - lo,
           "trace_window_s": hi - lo,
           "chips": chips,
           "busy_s": sum(c["busy_s"] for c in ran) / len(ran) if ran else 0.0,
           "idle_gaps": {}}
    if ran:
        busiest = max(ran, key=lambda c: c["busy_s"])
        idle = complement(busiest["busy"], lo, hi)
        for name, ivs in spans.items():
            if name != WINDOW_SPAN:
                out["idle_gaps"][name] = overlap(idle, clip(merge(ivs), lo, hi))
    for c in chips.values():
        del c["busy"]
    return out


def op_totals(summary: dict) -> dict:
    """{op name: [seconds, count]} summed over the chips."""
    acc = defaultdict(lambda: [0.0, 0])
    for chip in summary["chips"].values():
        for name, (sec, cnt) in chip["ops"].items():
            acc[name][0] += sec
            acc[name][1] += cnt
    return dict(acc)


def module_ops(summary: dict, module: str) -> dict:
    """{op: [seconds, count]} over all chips of the ops that ran inside the
    traced module `module`, and under "" the module's own [seconds, count];
    empty where the trace has no such module."""
    acc = defaultdict(lambda: [0.0, 0])
    for chip in summary["chips"].values():
        if module in chip["modules"]:
            for name, (sec, cnt) in [("", chip["modules"][module]),
                                     *chip["module_ops"].get(
                                         module, {}).items()]:
                acc[name][0] += sec
                acc[name][1] += cnt
    return dict(acc)


def idle_share_pct(busy_s: float, window_s: float) -> float:
    return 100.0 * (1.0 - busy_s / window_s)


def breakdown(summary: dict, gap_notes: dict | None = None) -> dict:
    """The result line's `breakdown`: the ten device ops that took most
    time (seconds a chip, mean over chips that ran) and the ten longest
    idle gaps by what the host was doing."""
    n = max(1, sum(1 for c in summary["chips"].values() if c["busy_s"] > 0))
    ops = sorted(((name, sec / n) for name, (sec, _) in
                  op_totals(summary).items()), key=lambda kv: -kv[1])[:10]
    gaps = dict(summary["idle_gaps"])
    gaps.update(gap_notes or {})
    gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k.replace(" ", "_"), v] for k, v in gaps]}
