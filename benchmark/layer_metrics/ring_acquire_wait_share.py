"""Waiting for a free staging-ring slot: share of the window, self time of the
program's spans (brpc_tpu.spans) ring.acquire. The five ring_*_share and the
loop's remainder sum to 100."""
from benchmark import stages

LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("ring.acquire",)


def read(obs):
    return stages.ring_self_share(obs, SPANS)
