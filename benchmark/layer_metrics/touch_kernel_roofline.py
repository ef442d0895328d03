"""The integrity pass against the HBM bound: least time for one read of
the chunk (and one write, where the pass's own module has a `copy` op: the
donated buffer copied out) at the table's HBM peak, over the device time of
the pass's ops per call. The pass is found by its traced module, `jit_` +
the name of the function the program jits (`touch`), not by a fusion name
the compiler makes up. A trace that has device planes and no such module
is an error, not a silent gap: the program renamed the pass."""
from benchmark import roofline, xplane

LAYER = "kernel (the jitted integrity pass, _touch_kernel)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "device_trace"

MODULE = "jit_touch"


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["chips"]:
        return None  # not traced, or no device plane (the CPU rehearsal)
    ops = xplane.module_ops(trace, MODULE)
    calls = ops.pop("", [0.0, 0])[1]
    seconds = sum(sec for sec, _ in ops.values())
    if not calls or seconds <= 0:
        seen = sorted({m for c in trace["chips"].values()
                       for m in c["modules"]})
        raise LookupError(f"no device op inside a module {MODULE!r} in "
                          f"the trace (modules seen: {seen})")
    copied = any("copy" in name for name in ops)
    least = roofline.touch_kernel_least_s(obs["chunk_bytes"], copied,
                                          obs["device_kind"])
    return roofline.share_pct(least, seconds / calls)
