"""Inputs from `--seed`: the same seed gives the same bytes, everywhere.

Two generators, both counter-based so numpy makes them in one expression:

- `body(seed, stream, nbytes)`: the echo payloads. `client/echo_load.cc`
  makes the same bytes in C++ (`PayloadWord`); the harness holds the
  client's digests to this module, so the two cannot drift apart unseen.
- `words(seed, stream, n)`: uint32 words for the ring's chunks.
"""
import zlib

import numpy as np

_M64 = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15
_STREAM = 0xD1B54A32D192ED03


def _mix64(z: np.ndarray) -> np.ndarray:
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def words64(seed: int, stream: int, n: int) -> np.ndarray:
    """uint64[n]: word j = mix64(seed*G + stream*S + (j+1)*G) mod 2**64."""
    base = np.uint64((seed * _GOLDEN + stream * _STREAM) & _M64)
    j = np.arange(1, n + 1, dtype=np.uint64)
    with np.errstate(over="ignore"):
        return _mix64(base + j * np.uint64(_GOLDEN))


def body(seed: int, stream: int, nbytes: int) -> bytes:
    """The seeded part of an echo payload: little-endian words64, cut to
    `nbytes`."""
    w = words64(seed, stream, (nbytes + 7) // 8)
    return w.astype("<u8").tobytes()[:nbytes]


def words(seed: int, stream: int, n: int) -> np.ndarray:
    """uint32[n] (the low and high halves of words64, in memory order)."""
    return words64(seed, stream, (n + 1) // 2).astype("<u8").view("<u4")[:n]


def echo_tag(caller: int, seq: int) -> bytes:
    """Bytes [0,8) of caller `caller`'s operation `seq` (seq counts from 1)."""
    return int((caller << 48) | seq).to_bytes(8, "little")


def echo_reply_crc32(seed: int, caller: int, seq: int, nbytes: int) -> int:
    """zlib crc32 of the reply that operation must get back."""
    return zlib.crc32(echo_tag(caller, seq) + body(seed, caller, nbytes - 8))


def bodies_crc32(seed: int, callers: int, nbytes: int) -> int:
    """Running zlib crc32 over every caller's body, caller 0 first."""
    crc = 0
    for c in range(callers):
        crc = zlib.crc32(body(seed, c, nbytes - 8), crc)
    return crc
