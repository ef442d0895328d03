"""CPU time of the server process over the window per 1e6 payload bytes
echoed and verified: the host cost of moving bytes, where bytes do the
work."""
LAYER = "host cost of the fabric (all of cpp/)"
UNIT = "us/MB"
MOVES = "goodput_gbps"
SOURCE = "host_clock"


def read(obs):
    if not obs.get("payload_bytes") or "server_cpu_s" not in obs:
        return None
    return obs["server_cpu_s"] * 1e6 / (obs["payload_bytes"] / 1e6)
