"""Mean of the CLIENT's tici.link_handoff over the window, for 1 MiB replies:
the server posts a reply's link descriptor -> the client's pump consumes it,
one sample a descriptor. Window-exact: the client's own cumulative table,
dumped by benchmark/client/echo_load.cc after its warm-up and after its drain,
after - before; None where the client sent no table."""
from benchmark import stages

LAYER = "pool / lease (cpp/tici)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

STAGE = "tici.link_handoff"


def read(obs):
    return stages.mean_us(obs, STAGE, side="client")
