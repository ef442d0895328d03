"""Mean of the server's tnet.consume_to_cut over the window exactly (cumulative
histogram, after - before): the request's first bytes consumed from the link
-> the whole message cut and its meta parsed. For 1 MiB messages: the copy
through the link."""
from benchmark import stages

LAYER = "transport (cpp/tnet)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

STAGE = "tnet.consume_to_cut"


def read(obs):
    return stages.mean_us(obs, STAGE)
