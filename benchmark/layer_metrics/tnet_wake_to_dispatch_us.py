"""p99 of rpc_dispatcher_wake_to_dispatch_us on the server's busiest epoll
loop (most events over the window), from /loops after the window.
`/loops?reset=1` resets only the run-queue high-waters, not this
recorder: it is the program's windowed recorder, so warm-up is included
where the window is shorter than the recorder's."""
LAYER = "transport (cpp/tnet)"
UNIT = "us"
MOVES = "p99_us"
SOURCE = "program_counter"


def read(obs):
    try:
        before = {l["loop"]: l["events"]
                  for l in obs["before"]["loops"]["loops"]}
        loops = obs["after"]["loops"]["loops"]
    except KeyError:
        return None
    busy = [l for l in loops if l["events"] - before.get(l["loop"], 0) > 0]
    if not busy:
        return None
    top = max(busy, key=lambda l: l["events"] - before.get(l["loop"], 0))
    return float(top["wake_to_dispatch_us"]["p99"])
