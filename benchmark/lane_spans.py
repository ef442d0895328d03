"""Readers' arithmetic over the served device leg's spans (brpc_tpu.spans,
in the harness's own process, which is the server's): self time a call.
`stages.py` keeps the window-exact stage arithmetic and the ring cell's
shares; this is the same reduction per call launched instead of per second
of the window. None where there is nothing to read."""

SERVED = "tensor.fill"  # one a served call; the ring pass has none


def per_call_us(obs: dict, names):
    """Self time of the spans `names` inside the window over the served
    calls launched in it (one `ring.launch` each), in microseconds; None
    without a window, without the program's spans, or where the window
    holds no served call (another driver's spans)."""
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
    calls = sum(1 for rec in records if rec[0] == "ring.launch")
    own = spans.self_times(records)
    return 1e6 * sum(own.get(n, 0.0) for n in names) / calls
