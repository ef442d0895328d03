"""Readers' arithmetic over the cache hand-off's spans (brpc_tpu.spans, in
the harness's own process, which is the server's): `lane_spans.py`'s
reduction for a call that crosses the lane as several chunks -- self time a
chunk (one `ring.launch` each) or a call (one `kv.reply` each), and the mean
length of the spans no one thread holds (`kv.join`). None where there is
nothing to read."""

SERVED = "kv.fill"  # one a chunk of a served Put; no other path has it


def _records(obs: dict):
    """The window's span records; None without a window, without the
    program's spans, or where the window holds no chunk of a Put."""
    t0, window_s = obs.get("t_first_op"), obs.get("window_s")
    if t0 is None or not window_s:
        return None
    try:
        from brpc_tpu import spans
    except ImportError:
        return None
    records = spans.snapshot(t0, t0 + window_s)
    if not any(rec[0] == SERVED for rec in records):
        return None
    return records


def self_us_per(obs: dict, names, per: str):
    """Self time of the spans `names` inside the window over the number of
    `per` spans in it, in microseconds."""
    records = _records(obs)
    if records is None:
        return None
    from brpc_tpu import spans

    count = sum(1 for rec in records if rec[0] == per)
    if not count:
        return None
    own = spans.self_times(records)
    return 1e6 * sum(own.get(n, 0.0) for n in names) / count


def mean_length_us(obs: dict, name: str):
    """Mean length of the window's `name` spans (clipped to it)."""
    records = _records(obs)
    if records is None:
        return None
    lengths = [end - start for n, start, end, *_ in records if n == name]
    if not lengths:
        return None
    return 1e6 * sum(lengths) / len(lengths)
