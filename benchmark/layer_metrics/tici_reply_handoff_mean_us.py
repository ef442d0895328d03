"""Mean of the CLIENT's tici.link_handoff over the window: the server posts a
reply's link descriptor (its stamp rides the descriptor) -> the client's pump
consumes it; the doorbell -> epoll -> input fiber -> pump leg of the reply,
one sample a descriptor. Window-exact: the client's own cumulative table,
dumped by benchmark/client/echo_load.cc after its warm-up and after its drain,
after - before; None where the client sent no table."""
from benchmark import stages

LAYER = "pool / lease (cpp/tici)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "tici.link_handoff"


def read(obs):
    return stages.mean_us(obs, STAGE, side="client")
