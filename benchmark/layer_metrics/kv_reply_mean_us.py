"""Answering a Put: self time of the program's span (brpc_tpu.spans) kv.reply
inside the window, in microseconds a call answered in it -- the completion
thread from "the last word is back" to the reply enqueued on the socket: the
layer marked present, word and admission number into the response, then
`done->Run()`. The span contains the C++ stage tdev.reply, which is another
quantity and is not given under this name."""
from benchmark import kv_spans

LAYER = "served cache hand-off (brpc_tpu/kv_service.py + DeviceLane + c_api pull server)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("kv.reply",)


def read(obs):
    return kv_spans.self_us_per(obs, SPANS, "kv.reply")
