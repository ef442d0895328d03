"""Drivers: one per way of driving the system under test. A configuration
file names its driver; each exposes `run(run) -> observation`.

An observation is a dict: `attempted`, `failed`, `window_s` (first timed
operation to the last completion), `t_first_op` (monotonic clock),
`memory_peak_bytes`, `end_to_end` {metric: value}, `checks` [(name,
value, limit)], `trace` (the xplane summary of a traced run, else None),
and whatever counters the per-layer readers of its cells read.
"""
