"""99th percentile of the server's tfiber.dispatch_to_handler over the window
exactly (cumulative histogram, after - before): request meta parsed ->
handler entered (inline on the input fiber, or on a fiber of its own)."""
from benchmark import stages

LAYER = "scheduler (cpp/tfiber)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "tfiber.dispatch_to_handler"


def read(obs):
    return stages.quantile_us(obs, STAGE, 0.99)
