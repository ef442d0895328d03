"""Host side of H2D and of dispatching the jitted integrity pass and the async
D2H: share of the window, self time of the program's spans (brpc_tpu.spans)
ring.h2d + ring.kernel_dispatch."""
from benchmark import stages

LAYER = "staging ring (cpp/tici DeviceStagingRing + brpc_tpu/device_path.py)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "program_span"

SPANS = ("ring.h2d", "ring.kernel_dispatch")


def read(obs):
    return stages.ring_self_share(obs, SPANS)
