"""Putting a chunk on the chip and stepping it: self time of the program's
spans (brpc_tpu.spans) ring.h2d (the taker's `device_put` of the slot) +
ring.kernel_dispatch (the dispatch thread's jitted kv_put_step and the request
for the word's copy back) inside the window, in microseconds a chunk launched
in it. Two threads' work, as tensor_launch_mean_us's since PR 32."""
from benchmark import kv_spans

LAYER = "served cache hand-off (brpc_tpu/kv_service.py + DeviceLane + c_api pull server)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("ring.h2d", "ring.kernel_dispatch")


def read(obs):
    return kv_spans.self_us_per(obs, SPANS, "ring.launch")
