"""The benchmark's own plain reference of kvpb.Cache, and the digests the
driver holds `client/kv_load.cc` to (as `tensor_reference.py` does for
`tensor_load.cc`). Nothing here imports the program (`brpc_tpu`,
`libtpurpc.so`) or takes anything it made.

A Put's request is B bytes (a multiple of 8): bytes 0-15 carry session,
layer, caller and operation number, the rest comes from the seed
(`payload.body`). Its reply's word is the wraparound sum of x[j] * (2j + 1)
over the request as little-endian uint32 words (`reference.integrity_word`,
the ring cell's). The pool has a fixed number of session slots: a session
is given one at its first Put, in the order the service says (`admitted`,
in every reply), and once all are taken each new session evicts the one
admitted longest ago, whole. Get of a layer present returns the bytes put;
of an evicted or unknown session, a not-found error.
"""
import numpy as np

from benchmark import payload, reference

NOT_FOUND = 2  # errno ENOENT: what a Get of what is not in the pool fails with
_M64 = (1 << 64) - 1


def session_id(seed: int, callers: int, caller: int, n: int) -> int:
    """Caller `caller`'s n-th session (n from 1), as kv_load.cc names it."""
    mixed = int(payload.words64(seed, callers + caller, n + 1)[n])
    return ((mixed & 0xFFFFFF) << 40) | (caller << 32) | n


def request(seed: int, session: int, layer: int, caller: int, seq: int,
            nbytes: int) -> bytes:
    """What caller `caller` sends as its operation `seq` (from 1)."""
    stamp = (session.to_bytes(8, "little") + layer.to_bytes(4, "little")
             + ((caller << 24) | (seq & 0xFFFFFF)).to_bytes(4, "little"))
    return stamp + payload.body(seed, caller, nbytes - 16)


def word(data: bytes) -> int:
    return reference.integrity_word(np.frombuffer(data, dtype="<u4"))


def resident_after(sessions, slots: int):
    """Replay of the admissions: `sessions` is [(session, admitted), ...]
    for every session ever acknowledged. Returns (resident, evicted,
    problems): the sessions the pool must hold now, those it must have
    evicted (oldest first), and how many admission numbers are missing,
    doubled or out of range (0 for a sound record)."""
    order = sorted(sessions, key=lambda s: s[1])
    numbers = [admitted for _, admitted in order]
    problems = sum(1 for i, a in enumerate(numbers) if a != i)
    problems += len(numbers) - len({s for s, _ in order})
    ids = [s for s, _ in order]
    cut = max(0, len(ids) - slots)
    return set(ids[cut:]), ids[:cut], problems


def judge(report: dict, seed: int, callers: int, nbytes: int) -> list:
    """The client's own counts and its digests against this module: the
    numbers compared, each with its limit (all exact: 0)."""
    digests_wrong = int(report["body_crc32"]
                        != payload.bodies_crc32(seed, callers, nbytes - 8))
    for c, (session, layer, seq, want) in enumerate(report["last_put"]):
        if seq and want != word(request(seed, session, layer, c, seq,
                                        nbytes)):
            digests_wrong += 1
        if seq and (session >> 32) & 0xFF != c:
            digests_wrong += 1
        if seq and session != session_id(seed, callers, c,
                                         session & 0xFFFFFFFF):
            digests_wrong += 1
    return [("replies_wrong", report["mismatched"], 0),
            ("replies_missing_or_error", report["rpc_failed"], 0),
            ("digests_wrong", digests_wrong, 0)]


def landed_short(acked_bytes: int, landed_bytes, chunks, executions) -> int:
    """Bytes acknowledged in the window that the device cannot be shown to
    hold: `acked_bytes` less the window's difference of the program's
    `rpc_kv_bytes_landed` (bytes of chunks whose word came back from the
    device; None where the program has no such counter: all short); and,
    where a trace with device planes was taken, every chunk the program
    counted (`rpc_kv_chunks`) beyond the executions of the step's module
    the trace shows counts as one byte more."""
    short = max(0, acked_bytes - int(landed_bytes or 0))
    if executions is not None:
        short += max(0, int(chunks or 0) - int(executions))
    return short


def readback_wrong(readback: dict, expected_checks: int,
                   evicted_session: int) -> int:
    """What the readback after the drain got wrong: layers that came back
    with other bytes or not at all, checks the client did not make, and a
    Get of an evicted session that was told anything but not-found."""
    wrong = readback["readback_wrong"] + readback["readback_failed"]
    wrong += max(0, expected_checks - readback["readback_checked"])
    if evicted_session and readback["evicted_code"] != NOT_FOUND:
        wrong += 1
    return wrong
