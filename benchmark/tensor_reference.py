"""The benchmark's own plain reference of tensor.Step, and the digests the
driver holds `client/tensor_load.cc` to (as `payload.py` does for
`echo_load.cc`). Nothing here imports the program (`brpc_tpu`,
`libtpurpc.so`) or takes anything it made.

Request: N bytes (a multiple of 8, at least 16) read as little-endian uint32
words x; bytes 0-7 carry caller and sequence number, the rest comes from the
seed (`payload.body`). Reply: N + 4 bytes, y ‖ w: y[0], y[1] = x[0], x[1];
y[j] = x[j] XOR key for j >= 2; w = the wraparound sum of x[j] * (2j + 1)
(`reference.integrity_word`, the ring cell's).
"""
import zlib

import numpy as np

from benchmark import payload, reference


def step(x_bytes: bytes, key: int) -> bytes:
    """The reply the service must give for the request `x_bytes`."""
    x = np.frombuffer(x_bytes, dtype="<u4")
    if x.nbytes < 16 or x.nbytes % 8:
        raise ValueError(f"a request of {x.nbytes} bytes")
    y = x.copy()
    y[2:] ^= np.uint32(key)
    return y.tobytes() + reference.integrity_word(x).to_bytes(4, "little")


def request(seed: int, caller: int, seq: int, nbytes: int) -> bytes:
    """What caller `caller` sends as its operation `seq` (from 1)."""
    return payload.echo_tag(caller, seq) + payload.body(seed, caller,
                                                        nbytes - 8)


def reply_crc32(seed: int, caller: int, seq: int, nbytes: int,
                key: int) -> int:
    """zlib crc32 of the reply that operation must get back."""
    return zlib.crc32(step(request(seed, caller, seq, nbytes), key))


def judge(report: dict, seed: int, callers: int, nbytes: int,
          key: int) -> list:
    """The client's own counts and its digests against this module: the
    numbers compared, each with its limit (all exact: 0)."""
    digests_wrong = int(report["body_crc32"]
                        != payload.bodies_crc32(seed, callers, nbytes))
    for c, (seq, crc) in enumerate(zip(report["last_seq"],
                                       report["last_reply_crc32"])):
        if crc != reply_crc32(seed, c, seq, nbytes, key):
            digests_wrong += 1
    return [("replies_wrong", report["mismatched"], 0),
            ("replies_missing_or_error", report["rpc_failed"], 0),
            ("digests_wrong", digests_wrong, 0)]


def device_calls_short(answered: int, through_lane, executions) -> int:
    """Calls answered in the window that the device cannot be shown to have
    made: `answered` less the window's difference of the
    program's `rpc_tensor_calls` (replies that came back through the lane;
    None where the program has no such counter: all short) and, where a
    trace with device planes was taken, less the executions of the step's
    module it shows, if that leaves more."""
    short = answered - int(through_lane or 0)
    if executions is not None:
        short = max(short, answered - int(executions))
    return max(0, short)
