"""A Put in the lane: mean length of the program's span (brpc_tpu.spans)
kv.join -- the call's first chunk submitted -> its last chunk's word back from
the device -- over the calls of the window. It begins on the taker and ends on
the completion thread, so it is a length, not a self time: what a call waits
for its chunks, the chunks of other calls between them included."""
from benchmark import kv_spans

LAYER = "served cache hand-off (brpc_tpu/kv_service.py + DeviceLane + c_api pull server)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPAN = "kv.join"


def read(obs):
    return kv_spans.mean_length_us(obs, SPAN)
