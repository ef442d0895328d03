"""What the launcher spends outside its named stages: share of the window,
self time of the program's spans (brpc_tpu.spans) ring.pass + ring.launch +
ring.drain -- the pass's loop, the hand-over to the completion thread and the
drain at each pass's end. Only the thread that calls `run()` leaves these
spans. With ring_acquire_wait_share, ring_stage_frame_share and
ring_h2d_dispatch_share the launcher's four add up to the window: since PR 26
the launcher is the critical path, and the completion thread runs beside it."""
from benchmark import stages

LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("ring.pass", "ring.launch", "ring.drain")


def read(obs):
    return stages.ring_self_share(obs, SPANS)
