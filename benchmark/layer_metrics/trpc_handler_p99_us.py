"""99th percentile of the server's trpc.handler over the window exactly
(cumulative histogram, after - before): handler entered -> done->Run()."""
from benchmark import stages

LAYER = "protocol / call (cpp/trpc)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "trpc.handler"


def read(obs):
    return stages.quantile_us(obs, STAGE, 0.99)
