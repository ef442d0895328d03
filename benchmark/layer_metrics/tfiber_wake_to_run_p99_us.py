"""99th percentile of the server's tfiber.wake_to_run over the window exactly
(cumulative histogram, after - before): a fiber made runnable (butex wake,
sleep over, background start, requeue) -> running on a worker. Every fiber
of the process, not only the RPC's: in the echo server nearly all samples are
the input fiber started for a doorbell, and that wait also lies inside
tici.link_handoff (post -> pump), so the two metrics overlap and do not
add."""
from benchmark import stages

LAYER = "scheduler (cpp/tfiber)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "tfiber.wake_to_run"


def read(obs):
    return stages.quantile_us(obs, STAGE, 0.99)
