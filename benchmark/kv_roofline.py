"""The work the cache hand-off's put step's roofline counts (`roofline.py`
keeps the peaks and the arithmetic): of the work the CALLER asked for, not
of what today's lowering moves, and the same whatever implements the step."""
from benchmark import roofline

MODULE = "jit_kv_put_step"  # `jit_` + the function the program jits


def kv_put_step_bytes(chunk_bytes: int) -> int:
    """HBM bytes one chunk of a Put needs: one read of the chunk where the
    DMA left it and one write of it into the pool. Reading it back out of
    the pool for the word is the program's way of proving the write, not
    work the caller asked for; the pool around it is never touched. The
    4-byte word is not counted."""
    return 2 * chunk_bytes


def kv_put_step_least_s(chunk_bytes: int, device_kind: str) -> float:
    return (kv_put_step_bytes(chunk_bytes)
            / roofline.peaks(device_kind)["hbm_bytes_per_s"])
