"""Mean of the server's tnet.write_queue over the window exactly (cumulative
histogram, after - before): reply frame enqueued -> its last byte posted to
the link. For 1 MiB replies: the posting of all their descriptors, credit
waits included."""
from benchmark import stages

LAYER = "transport (cpp/tnet)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"

STAGE = "tnet.write_queue"


def read(obs):
    return stages.mean_us(obs, STAGE)
