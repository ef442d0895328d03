"""Lost wake-ups picked up by a safety net in the CLIENT process inside the
window: the sum of its cumulative `*_found_work` counters, after - before (a
worker's 100 ms park ended by its timeout with a runnable fiber waiting; the
writer's credit wait and EPOLLOUT wait ended by their timed re-check with
room to write), dumped by benchmark/client/echo_load.cc after its warm-up
and after its drain. 0 is healthy; each one is a stall of up to the net's
period. None where the client sent no counters; the server's are
tfiber_rescued_wakeups."""
from benchmark import stages

LAYER = "scheduler (cpp/tfiber)"
UNIT = "count"
MOVES = "p99_us"
SOURCE = "program_counter"


def read(obs):
    return stages.counters_delta(obs, "_found_work", side="client")
