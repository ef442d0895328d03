"""How much of the retire side of the ring pass ran beside a launch: time
inside the program's spans (brpc_tpu.spans) ring.retire that lies inside a
ring.launch of another thread, in % of the time inside ring.retire, both
clipped to the window. 0 where one thread does both in turn; None where the
window holds no ring.retire. A share of the retire work, not of a peak."""
from benchmark import xplane

LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"


def read(obs):
    t0, window_s = obs.get("t_first_op"), obs.get("window_s")
    if t0 is None or not window_s:
        return None
    try:
        from brpc_tpu import spans
    except ImportError:
        return None
    launches, retires = {}, {}  # thread -> [(start, end)]
    for name, start, end, _, thread in spans.snapshot(t0, t0 + window_s):
        if name == "ring.launch":
            launches.setdefault(thread, []).append((start, end))
        elif name == "ring.retire":
            retires.setdefault(thread, []).append((start, end))
    inside = beside = 0.0
    for thread, own in retires.items():
        own = xplane.merge(own)
        others = xplane.merge(span for other, theirs in launches.items()
                              if other != thread for span in theirs)
        inside += xplane.total(own)
        beside += xplane.overlap(own, others)
    return 100.0 * beside / inside if inside > 0 else None
