"""Share of the payload bytes that rode as pool-block references instead
of inline bytes: the server's rpc_pool_descriptor_send_bytes delta over
the payload bytes it sent back. 0 on today's inline-attachment path (the
counter does not exist until a descriptor is sent), which is a reading:
every byte was copied."""
LAYER = "pool / lease (cpp/tici)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_counter"

COUNTER = "rpc_pool_descriptor_send_bytes"


def read(obs):
    if not obs.get("payload_bytes") or "after" not in obs:
        return None
    sent = (obs["after"]["vars"].get(COUNTER, 0.0)
            - obs["before"]["vars"].get(COUNTER, 0.0))
    return 100.0 * sent / obs["payload_bytes"]
