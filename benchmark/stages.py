"""Readers' arithmetic over the program's own stage clock and spans.

The server publishes cumulative per-stage histograms under "stages" in
`/status?format=json` (cpp/tvar/stage_recorder.h): `after - before` of two
scrapes is exactly what happened between them, so these numbers are the
window's and hold nothing of the warm-up. The client process has the same
table and the same cumulative counters of its own; the load generator
dumps both after its warm-up and after its drain, and the driver hands
them on as `client_before` / `client_after` in a scrape's shape. `side`
says whose pair is read: one process's table never stands in for the
other's. The ring pass leaves spans in `brpc_tpu.spans`, in the harness's
own process, cut here to the window. Every function returns None where
there is nothing to read: an empty window, a program without the stage
clock (the parent of the PR that brought it), a client that sent no
table, an `obs` of another driver.
"""

SUB = 8  # sub-buckets an octave: PercentileHistogram's layout

# The server's stages between a request's first consumed byte and its
# reply's post, in order. Each ends on the stamp the next starts from, so
# their means add up to the call's residence in the server.
RESIDENCE = ("tnet.consume_to_cut", "tfiber.dispatch_to_handler",
             "trpc.handler", "trpc.respond", "tnet.write_queue")
# side -> the observation's keys of that process's two dumps
SIDES = {"server": ("before", "after"),
         "client": ("client_before", "client_after")}


def bucket_value(index: int) -> int:
    """Representative value of a bucket, as PercentileHistogram::
    bucket_value (cpp/tvar/percentile.h): exact under 16, the octave
    slice's midpoint above."""
    if index < SUB:
        return index
    octave, sub = divmod(index, SUB)
    base = 1 << octave
    return base + (base // 8) * sub + base // 16


def window(obs: dict, stage: str, side: str = "server"):
    """The stage's samples inside the window, in the `side` process:
    {"count", "sum_us", "buckets": {index: count}}, or None."""
    first, last = SIDES[side]
    try:
        before = obs[first]["status"]["stages"][stage]
        after = obs[last]["status"]["stages"][stage]
    except (KeyError, TypeError):
        return None
    count = after["count"] - before["count"]
    if count <= 0:
        return None
    was = dict(map(tuple, before["buckets"]))
    buckets = {}
    for index, n in after["buckets"]:
        n -= was.get(index, 0)
        if n > 0:
            buckets[index] = n
    return {"count": count, "sum_us": after["sum_us"] - before["sum_us"],
            "buckets": buckets}


def mean_us(obs: dict, stage: str, side: str = "server"):
    w = window(obs, stage, side)
    return None if w is None else w["sum_us"] / w["count"]


def residence_mean_us(obs: dict):
    """Mean time a call spent in the server over the window: the sum of
    the RESIDENCE stages' means; None where any of them is missing."""
    means = [mean_us(obs, stage) for stage in RESIDENCE]
    return None if None in means else sum(means)


def quantile_us(obs: dict, stage: str, q: float, side: str = "server"):
    """As HistogramSnapshot::quantile: the bucket holding the sample of
    rank floor(q * count), by its representative value."""
    w = window(obs, stage, side)
    if w is None or not w["buckets"]:
        return None
    total = sum(w["buckets"].values())
    target = min(int(q * total), total - 1)
    seen = 0
    for index in sorted(w["buckets"]):
        seen += w["buckets"][index]
        if seen > target:
            return float(bucket_value(index))
    return None


def counters_delta(obs: dict, suffix: str, side: str = "server"):
    """Sum over the window of every cumulative /vars integer of the `side`
    process whose name ends in `suffix`; None where it has none."""
    first, last = SIDES[side]
    try:
        before, after = obs[first]["vars"], obs[last]["vars"]
    except (KeyError, TypeError):
        return None
    names = [n for n in after if n.endswith(suffix)]
    if not names:
        return None
    return float(sum(after[n] - before.get(n, 0) for n in names))


def ring_self_share(obs: dict, names):
    """Self time of the ring pass's spans `names` inside the window, in %
    of the window (brpc_tpu.spans is imported only once `obs` is known to
    hold a window)."""
    t0, window_s = obs.get("t_first_op"), obs.get("window_s")
    if t0 is None or not window_s:
        return None
    try:
        from brpc_tpu import spans
    except ImportError:
        return None
    records = spans.snapshot(t0, t0 + window_s)
    if not records:
        return None
    own = spans.self_times(records)
    return 100.0 * sum(own.get(n, 0.0) for n in names) / window_s
