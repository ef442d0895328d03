"""The tensor step against the HBM bound: least time for one read and one
write of the payload at the table's HBM peak (benchmark/tensor_roofline.py),
over the device time of the ops inside the step's traced module a call. The
module is `jit_` + the name of the function the program jits (`tensor_step`),
not a fusion name the compiler makes up. A trace that has device planes and no
such module is an error, not a silent gap: the program renamed the step."""
from benchmark import roofline, tensor_roofline, xplane

LAYER = "kernel (the jitted tensor step, _tensor_step_kernel)"
UNIT = "%"
MOVES = "goodput_gbps"
SOURCE = "device_trace"


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["chips"]:
        return None  # not traced, or no device plane (the CPU rehearsal)
    ops = xplane.module_ops(trace, tensor_roofline.MODULE)
    calls = ops.pop("", [0.0, 0])[1]
    seconds = sum(sec for sec, _ in ops.values())
    if not calls or seconds <= 0:
        seen = sorted({m for c in trace["chips"].values()
                       for m in c["modules"]})
        raise LookupError(f"no device op inside a module "
                          f"{tensor_roofline.MODULE!r} in the trace "
                          f"(modules seen: {seen})")
    least = tensor_roofline.tensor_step_least_s(obs["bytes_each"],
                                                obs["device_kind"])
    return roofline.share_pct(least, seconds / calls)
