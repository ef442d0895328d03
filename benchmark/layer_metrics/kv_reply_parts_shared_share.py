"""Share of the reply parts written in the window that lay whole in the
shared region, in %: the server's /vars rpc_reply_parts_shared over
rpc_reply_parts_shared + rpc_reply_parts_local, each the difference of the
scrapes that bracket the window. A shared part is posted to the peer by
reference; a local one is bounce-copied by the link as the reply is sent.
None where no part was written in the window, or from a program without
the counters."""

LAYER = "pool / lease (cpp/tici)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_counter"

SHARED, LOCAL = "rpc_reply_parts_shared", "rpc_reply_parts_local"


def read(obs):
    try:
        before, after = obs["before"]["vars"], obs["after"]["vars"]
        shared = after[SHARED] - before.get(SHARED, 0.0)
        local = after[LOCAL] - before.get(LOCAL, 0.0)
    except (KeyError, TypeError):
        return None
    if shared + local <= 0:
        return None
    return 100.0 * shared / (shared + local)
