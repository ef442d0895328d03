"""The server's own p99 of benchpb.EchoService/Echo (handler entry to
response written), from /status?format=json after the window. The
recorder is the program's windowed LatencyRecorder: it covers the last
seconds of the window and cannot be reset from outside, so a short run
still holds part of the warm-up."""
LAYER = "protocol / call (cpp/trpc)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_counter"

METHOD = "benchpb.EchoService.Echo"


def read(obs):
    try:
        status = obs["after"]["status"]["methods"][METHOD]
    except KeyError:
        return None
    if not status["count"]:
        return None
    return float(status["latency_us"]["p99"])
