"""Waiting for a credit and a free staging-ring slot: share of the window, self
time of the program's spans (brpc_tpu.spans) ring.acquire. One of the
launcher's four shares, which add up to the window (see
ring_launcher_rest_share)."""
from benchmark import stages

LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("ring.acquire",)


def read(obs):
    return stages.ring_self_share(obs, SPANS)
