"""Answering a call: self time of the program's span tensor.reply inside the
window, in microseconds a call launched in it -- the completion thread from
"D2H is back" to the reply enqueued on the socket: y and the word copied into
the response attachment, then `done->Run()`. The span contains the C++ stage
tdev.reply (`tpurpc_call_reply` entered -> reply enqueued), which is another
quantity and is not given under this name: None where the window holds no
such span."""
from benchmark import lane_spans

LAYER = "served device leg (brpc_tpu/tensor_service.py + DeviceLane + c_api pull server)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("tensor.reply",)


def read(obs):
    return lane_spans.per_call_us(obs, SPANS)
