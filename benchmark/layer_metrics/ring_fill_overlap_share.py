"""How much of the submitter's side of the ring pass ran beside a dispatch:
time inside the program's spans (brpc_tpu.spans) ring.launch -- a credit, a
slot, the one pass over a chunk's bytes, the frame, the H2D -- that lies
inside a ring.dispatch of another thread (the jitted call, the requests for
the copies back), in % of the time inside ring.launch, both clipped to the
window. 0 where one thread does both in turn; None where the window holds
no ring.launch, or no ring.dispatch (a program from before ISSUE 32, which
has no such span: nothing to read). A share of the launch work, not of a
peak."""
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
    launches, dispatches = {}, {}  # thread -> [(start, end)]
    for name, start, end, _, thread in spans.snapshot(t0, t0 + window_s):
        if name == "ring.launch":
            launches.setdefault(thread, []).append((start, end))
        elif name == "ring.dispatch":
            dispatches.setdefault(thread, []).append((start, end))
    inside = beside = 0.0
    for thread, own in launches.items():
        own = xplane.merge(own)
        others = xplane.merge(span for other, theirs in dispatches.items()
                              if other != thread for span in theirs)
        inside += xplane.total(own)
        beside += xplane.overlap(own, others)
    if not dispatches or inside <= 0:
        return None
    return 100.0 * beside / inside
