"""Mean of the server's stage tdev.take_wait over the window exactly (cumulative
histogram, after - before): the C++ handler parked the call -> the taker
thread's `tpurpc_server_take` got it. Calls queue here behind the one
launcher; it lies inside trpc.handler."""
from benchmark import stages

LAYER = "served device leg (brpc_tpu/tensor_service.py + DeviceLane + c_api pull server)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

STAGE = "tdev.take_wait"


def read(obs):
    return stages.mean_us(obs, STAGE)
