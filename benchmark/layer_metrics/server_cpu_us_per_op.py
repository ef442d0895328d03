"""CPU time of the server process (/proc/<pid>/stat, user + system, 10 ms
ticks) over the window, per verified operation: the host cores the whole
of cpp/ burns on the serving side of a small message."""
LAYER = "host cost of the fabric (all of cpp/)"
UNIT = "us/op"
MOVES = "qps"
SOURCE = "host_clock"


def read(obs):
    if not obs.get("ops") or "server_cpu_s" not in obs:
        return None
    return obs["server_cpu_s"] * 1e6 / obs["ops"]
