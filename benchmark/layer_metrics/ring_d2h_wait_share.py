"""Blocked until the device has answered and the bytes are back on the host:
share of the window, self time of the program's spans (brpc_tpu.spans)
ring.d2h_wait."""
from benchmark import stages

LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("ring.d2h_wait",)


def read(obs):
    return stages.ring_self_share(obs, SPANS)
