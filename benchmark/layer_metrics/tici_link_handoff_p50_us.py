"""Median of the server's tici.link_handoff over the window exactly (cumulative
histogram, after - before): the client posts a link descriptor (its stamp
rides the descriptor) -> the server's pump consumes it. The doorbell ->
epoll -> input fiber -> pump leg, one sample a descriptor."""
from benchmark import stages

LAYER = "pool / lease (cpp/tici)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "tici.link_handoff"


def read(obs):
    return stages.quantile_us(obs, STAGE, 0.5)
