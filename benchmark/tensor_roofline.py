"""The work the tensor step's roofline counts (`roofline.py` keeps the
peaks and the arithmetic): of the work the CALLER asked for, not of what
today's lowering moves."""
from benchmark import roofline

MODULE = "jit_tensor_step"  # `jit_` + the function the program jits


def tensor_step_bytes(payload_bytes: int) -> int:
    """HBM bytes one tensor.Step needs: one read of the request (y and the
    integrity word are made from the same pass over x) and one write of y.
    The 4-byte word is not counted."""
    return 2 * payload_bytes


def tensor_step_least_s(payload_bytes: int, device_kind: str) -> float:
    return (tensor_step_bytes(payload_bytes)
            / roofline.peaks(device_kind)["hbm_bytes_per_s"])
