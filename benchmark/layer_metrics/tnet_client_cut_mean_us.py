"""Mean of the CLIENT's tnet.consume_to_cut over the window: the read that
brought a reply's first bytes -> the reply cut and its meta parsed.
Window-exact: the client's own cumulative table, dumped by
benchmark/client/echo_load.cc after its warm-up and after its drain, after -
before; None where the client sent no table."""
from benchmark import stages

LAYER = "transport (cpp/tnet)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "tnet.consume_to_cut"


def read(obs):
    return stages.mean_us(obs, STAGE, side="client")
