"""Mean of the CLIENT's trpc.issue over the window: `CallMethod` entered ->
the request enqueued in `Socket::Write` (meta built, attachment appended, the
caller fiber's own time). Window-exact: the client's own cumulative table,
dumped by benchmark/client/echo_load.cc after its warm-up and after its drain,
after - before; None where the client sent no table."""
from benchmark import stages

LAYER = "protocol / call (cpp/trpc)"
UNIT = "us"
MOVES = "qps"
SOURCE = "program_span"

STAGE = "trpc.issue"


def read(obs):
    return stages.mean_us(obs, STAGE, side="client")
