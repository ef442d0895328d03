"""How much of a call's residence in the server is the device leg: the mean of
the server's stage trpc.handler (handler entered -> `done->Run()`: the wait to
be taken, the copy into the slot, frame, H2D, the jitted step, D2H and the
copy into the reply) over the mean of the five stages' sum
(benchmark.stages.RESIDENCE), both over the window exactly (cumulative
histograms, after - before), in %. Over 50 means the leg, not the link, sets
the pace of a served tensor call."""
from benchmark import stages

LAYER = "served device leg (brpc_tpu/tensor_service.py + DeviceLane + c_api pull server)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"

STAGE = "trpc.handler"


def read(obs):
    handler = stages.mean_us(obs, STAGE)
    residence = stages.residence_mean_us(obs)
    if handler is None or not residence:
        return None
    return 100.0 * handler / residence
