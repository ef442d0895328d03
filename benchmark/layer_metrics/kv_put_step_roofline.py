"""The put step against the HBM bound: least time for one read and one write
of a chunk's bytes at the table's HBM peak (benchmark/kv_roofline.py), over
the device time of the ops inside the step's traced module a chunk. The module
is `jit_` + the name of the function the program jits (`kv_put_step`), not a
fusion name the compiler makes up. None, never 0, where the trace has no such
module (a program without the step) or no device plane."""
from benchmark import kv_roofline, roofline, xplane

LAYER = "kernel (the jitted cache put step, _kv_put_kernel)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "device_trace"


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["chips"] or "chunk_bytes" not in obs:
        return None  # not traced, or no device plane (the CPU rehearsal)
    ops = xplane.module_ops(trace, kv_roofline.MODULE)
    chunks = ops.pop("", [0.0, 0])[1]
    seconds = sum(sec for sec, _ in ops.values())
    if not chunks or seconds <= 0:
        return None
    least = kv_roofline.kv_put_step_least_s(obs["chunk_bytes"],
                                            obs["device_kind"])
    return roofline.share_pct(least, seconds / chunks)
