"""Mean time a call spent in the server over the window exactly (cumulative
histograms, after - before): the request's first bytes consumed from the link
-> its reply posted to the link. The sum of the means of the five server
stages between them (benchmark.stages.RESIDENCE), which share their stamps;
what is left of the caller's latency is the two link hand-offs and the
client."""
from benchmark import stages

LAYER = "protocol / call (cpp/trpc)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_span"


def read(obs):
    return stages.residence_mean_us(obs)
