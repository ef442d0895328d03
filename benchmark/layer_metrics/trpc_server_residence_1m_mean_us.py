"""Mean time a call spent in the server over the window exactly (cumulative
histograms, after - before) in the 1 MiB cell (a name of its own: the two
echo cells report different end-to-end metrics): first bytes consumed -> reply
posted, the sum of the five server stages' means
(benchmark.stages.RESIDENCE)."""
from benchmark import stages

LAYER = "protocol / call (cpp/trpc)"
UNIT = "us"
MOVES = "goodput_gbps"
SOURCE = "program_span"


def read(obs):
    return stages.residence_mean_us(obs)
