"""Lost wake-ups picked up by a safety net inside the window: the sum of the
server's cumulative `*_found_work` counters, after - before (a worker's 100
ms park ended by its timeout with a runnable fiber waiting; the writer's
credit wait and EPOLLOUT wait ended by their timed re-check with room to
write). 0 is healthy; each one is a stall of up to the net's period."""
from benchmark import stages

LAYER = "scheduler (cpp/tfiber)"
UNIT = "count"
MOVES = "p99_us"
SOURCE = "program_counter"


def read(obs):
    return stages.counters_delta(obs, "_found_work")
