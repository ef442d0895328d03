"""The taker's one pass over a chunk's bytes: self time of the program's span
(brpc_tpu.spans) kv.fill -- the request attachment from the chunk's offset
into the ring slot, zero tail and crc32c in the same walk -- inside the
window, in microseconds a chunk launched in it (one ring.launch a chunk)."""
from benchmark import kv_spans

LAYER = "served cache hand-off (brpc_tpu/kv_service.py + DeviceLane + c_api pull server)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("kv.fill",)


def read(obs):
    return kv_spans.self_us_per(obs, SPANS, "ring.launch")
