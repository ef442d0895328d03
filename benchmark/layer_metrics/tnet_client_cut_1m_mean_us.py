"""Mean of the CLIENT's tnet.consume_to_cut over the window, for 1 MiB
replies: the read that brought a reply's first bytes -> the reply cut; the
copy of the reply out of the link. Window-exact: the client's own cumulative
table, dumped by benchmark/client/echo_load.cc after its warm-up and after its
drain, after - before; None where the client sent no table."""
from benchmark import stages

LAYER = "transport (cpp/tnet)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

STAGE = "tnet.consume_to_cut"


def read(obs):
    return stages.mean_us(obs, STAGE, side="client")
