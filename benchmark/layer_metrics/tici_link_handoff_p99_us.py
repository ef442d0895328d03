"""99th percentile of the server's tici.link_handoff over the window exactly
(cumulative histogram, after - before): descriptor posted by the client ->
consumed by the server's pump."""
from benchmark import stages

LAYER = "pool / lease (cpp/tici)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "tici.link_handoff"


def read(obs):
    return stages.quantile_us(obs, STAGE, 0.99)
