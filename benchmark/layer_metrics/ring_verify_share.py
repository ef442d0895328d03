"""crc32c of the returned bytes against the framer's: share of the window, self
time of the program's spans (brpc_tpu.spans) ring.verify. Since PR 26 the
completion thread's work, beside the launcher's four shares and not one of
them."""
from benchmark import stages

LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("ring.verify",)


def read(obs):
    return stages.ring_self_share(obs, SPANS)
