"""Deepest run queue any server scheduler pool reached inside the window:
/loops' runq_highwater, reset (`/loops?reset=1`) at the window's start."""
LAYER = "scheduler (cpp/tfiber)"
UNIT = "fibers"
MOVES = "p99_us"
SOURCE = "program_counter"


def read(obs):
    try:
        pools = obs["after"]["loops"]["pools"]
    except KeyError:
        return None
    if not pools:
        return None
    return float(max(p["runq_highwater"] for p in pools))
