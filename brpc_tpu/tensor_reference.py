"""The plain reference of the served call tensor.Step (ISSUE 29).

Request: N bytes, N a multiple of 8 and at least 16, read as little-endian
uint32 words x[0..N/4). Reply: N + 4 bytes, y ‖ w, where y[0], y[1] = x[0],
x[1] (caller and sequence number), y[j] = x[j] XOR key for every j >= 2,
and w is the wraparound sum over j of x[j] * (2j + 1), little-endian.

Straight numpy over Python-sized integers: no jax, no ring, no batching,
and nothing of the served path (`tensor_service`, `device_path`, the
native library) is imported here.
"""
import numpy as np


def check_size(nbytes: int) -> None:
    if nbytes < 16 or nbytes % 8:
        raise ValueError(f"a tensor.Step request is a multiple of 8 bytes "
                         f"and at least 16, not {nbytes}")


def step(x_bytes, key: int) -> bytes:
    """The reply tensor.Step must give for the request `x_bytes`."""
    x = np.frombuffer(bytes(x_bytes), dtype="<u4")
    check_size(x.nbytes)
    y = x.copy()
    y[2:] ^= np.uint32(key)
    mult = np.arange(x.size, dtype=np.uint64) * np.uint64(2) + np.uint64(1)
    prod = (x.astype(np.uint64) * mult) & np.uint64(0xFFFFFFFF)
    word = int(prod.sum(dtype=np.uint64)) & 0xFFFFFFFF
    return y.tobytes() + word.to_bytes(4, "little")
