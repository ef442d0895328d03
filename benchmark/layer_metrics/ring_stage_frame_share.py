"""Copying the chunk into its slot and framing it in place (header, meta,
crc32c): share of the window, self time of the program's spans
(brpc_tpu.spans) ring.stage + ring.frame."""
from benchmark import stages

LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("ring.stage", "ring.frame")


def read(obs):
    return stages.ring_self_share(obs, SPANS)
