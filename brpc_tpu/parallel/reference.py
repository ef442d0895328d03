"""Plain numpy references for the collective data plane.

Independent of jax and of the C++ engine: the same fills, checksums and
collectives written the obvious way, so `tests/test_collectives.py`, the
driver's dryrun and `chip_smoke.py` can all demand bit-exact agreement
from the XLA lowerings in `collective_echo.py` (and, through the golden
values, from `cpp/trpc/collective.cc`).
"""
import numpy as np


def fill_deterministic(seq: int, key: int, n: int) -> np.ndarray:
    """Twin of CollectiveEngine::FillDeterministic (uint32 wrap):
    word(i) = 0x9E3779B1*seq + 0x85EBCA77*key + 0xC2B2AE35*i."""
    i = np.arange(n, dtype=np.uint64)
    base = (0x9E3779B1 * (seq & 0xFFFFFFFF) +
            0x85EBCA77 * (key & 0xFFFFFFFF)) & 0xFFFFFFFF
    return ((base + 0xC2B2AE35 * i) & 0xFFFFFFFF).astype(np.uint32)


def fill_rows(seq: int, n_rows: int, words: int) -> np.ndarray:
    """uint32[n_rows, words], row r = fill_deterministic(seq, r, words)."""
    return np.stack([fill_deterministic(seq, r, words) for r in range(n_rows)])


def coll_checksum(words) -> int:
    """Twin of CollectiveEngine::Checksum == collective_echo's adler frame
    checksum of ONE row (uint32 WRAPAROUND cumsum, mod 65521)."""
    w = np.asarray(words, dtype=np.uint32)
    lo = w & np.uint32(0xFFFF)
    hi = w >> np.uint32(16)
    halves = np.stack([lo, hi], axis=-1).reshape(-1).astype(np.uint64)
    s1 = np.cumsum(halves) & 0xFFFFFFFF
    a = int(s1[-1]) % 65521
    b = int(np.sum(s1 % 65521)) % 65521
    return (b << 16) | a


def row_checksums(rows: np.ndarray) -> np.ndarray:
    return np.array([coll_checksum(r) for r in rows], dtype=np.uint32)


def allreduce(x: np.ndarray) -> np.ndarray:
    """Every row holds the uint32 wraparound sum over rows."""
    return np.tile(x.sum(axis=0, dtype=np.uint32), (x.shape[0], 1))


def allgather(x: np.ndarray) -> np.ndarray:
    """Every row holds all rows concatenated in rank order."""
    return np.tile(x.reshape(-1), (x.shape[0], 1))


def alltoall(x: np.ndarray) -> np.ndarray:
    """Block i of row r lands as block r of row i."""
    n = x.shape[0]
    return x.reshape(n, n, -1).transpose(1, 0, 2).reshape(n, -1)
