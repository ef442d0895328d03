"""Responses that left the server in a coalesced write, as a share of the
responses written over the window: rpc_socket_coalesced_writes (delta of
/loops' counter) over the Echo method's count delta (/status)."""
LAYER = "transport (cpp/tnet)"
UNIT = "%"
MOVES = "qps"
SOURCE = "program_counter"

METHOD = "benchpb.EchoService.Echo"


def read(obs):
    try:
        writes = (obs["after"]["loops"]["counters"]["coalesced_writes"]
                  - obs["before"]["loops"]["counters"]["coalesced_writes"])
        served = (obs["after"]["status"]["methods"][METHOD]["count"]
                  - obs["before"]["status"]["methods"][METHOD]["count"])
    except KeyError:
        return None
    if served <= 0:
        return None
    return 100.0 * writes / served
