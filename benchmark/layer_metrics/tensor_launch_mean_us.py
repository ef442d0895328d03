"""The launcher's critical path a call: self time of the program's spans
(brpc_tpu.spans) tensor.fill (request attachment -> ring slot) + ring.frame +
ring.h2d + ring.kernel_dispatch inside the window, in microseconds a call
launched in it (one ring.launch a call). One thread launches every call, so
1e6 over this is the most calls a second the lane can take."""
from benchmark import lane_spans

LAYER = "served device leg (brpc_tpu/tensor_service.py + DeviceLane + c_api pull server)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("tensor.fill", "ring.frame", "ring.h2d", "ring.kernel_dispatch")


def read(obs):
    return lane_spans.per_call_us(obs, SPANS)
