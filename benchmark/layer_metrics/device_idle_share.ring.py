"""1 - (union of device-op intervals over the traced window), on the one
chip the ring drives."""
from benchmark import xplane

LAYER = "device"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "device_trace"


def read(obs):
    trace = obs.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    return xplane.idle_share_pct(trace["busy_s"], trace["window_s"])
