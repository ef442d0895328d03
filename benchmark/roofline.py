"""Peaks by `device_kind`, and the work each roofline counts.

The bytes and times here are of the work the CALLER asked for, not of what
today's lowering moves, so a later PR that changes the lowering is read by
the same yardstick.
"""
import json
from pathlib import Path

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The table's row for this device; a device not in it is an error,
    never a default."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in {_PEAKS.name} "
            f"(known: {sorted(table)}): add its published peaks, with "
            "their source, before reporting a share of them")
    return table[device_kind]


def touch_kernel_bytes(chunk_bytes: int, writes_copy: bool) -> int:
    """HBM bytes the integrity pass needs for one chunk: one read of the
    chunk; one write more only where the trace shows the (donated) buffer
    is copied out instead of passed through."""
    return chunk_bytes * (2 if writes_copy else 1)


def touch_kernel_least_s(chunk_bytes: int, writes_copy: bool,
                         device_kind: str) -> float:
    return (touch_kernel_bytes(chunk_bytes, writes_copy)
            / peaks(device_kind)["hbm_bytes_per_s"])


def share_pct(least_s: float, measured_s: float) -> float:
    """Roofline share in %, never clipped: above 100 means the work is
    counted too high or the time leaves part of it out."""
    if measured_s <= 0:
        raise ValueError(f"measured time {measured_s} s")
    return 100.0 * least_s / measured_s
