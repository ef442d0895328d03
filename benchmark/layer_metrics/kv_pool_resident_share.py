"""How full the device pool is at the window's end: the program's /vars
rpc_kv_resident_bytes (bytes of the layers present) over rpc_kv_pool_bytes
(layers x sessions x layer bytes on the device), in %: that the chip's memory
is as full as the deployment's, and stays so under eviction. None from a
program without the counters."""

LAYER = "served cache hand-off (brpc_tpu/kv_service.py + DeviceLane + c_api pull server)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_counter"


def read(obs):
    try:
        after = obs["after"]["vars"]
        pool = after["rpc_kv_pool_bytes"]
        resident = after["rpc_kv_resident_bytes"]
    except (KeyError, TypeError):
        return None
    if not pool:
        return None
    return 100.0 * resident / pool
