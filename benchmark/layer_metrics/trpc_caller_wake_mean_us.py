"""Mean of the CLIENT's trpc.caller_wake over the window: `EndRPC`'s stamp ->
the synchronous caller fiber running again after `id_join` (the scheduler's
wake of the caller, in the client process). Window-exact: the client's own
cumulative table, dumped by benchmark/client/echo_load.cc after its warm-up
and after its drain, after - before; None where the client sent no table."""
from benchmark import stages

LAYER = "scheduler (cpp/tfiber)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"

STAGE = "trpc.caller_wake"


def read(obs):
    return stages.mean_us(obs, STAGE, side="client")
