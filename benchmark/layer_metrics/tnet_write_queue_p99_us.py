"""99th percentile of the server's tnet.write_queue over the window exactly
(cumulative histogram, after - before): reply frame enqueued in
Socket::Write -> its last byte posted to the link (the single-writer hand-
off, the round's coalescing, KeepWrite)."""
from benchmark import stages

LAYER = "transport (cpp/tnet)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "tnet.write_queue"


def read(obs):
    return stages.quantile_us(obs, STAGE, 0.99)
