"""CPU time the benchmark's own client process spent per verified
operation over the window (getrusage, user + system): what the load
generator itself costs beside the fabric's client side."""
LAYER = "load generator (benchmark's own)"
UNIT = "us/op"
MOVES = "qps"
SOURCE = "host_clock"


def read(obs):
    if not obs.get("ops") or "client_cpu_s" not in obs:
        return None
    return obs["client_cpu_s"] * 1e6 / obs["ops"]
