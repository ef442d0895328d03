"""The plain reference of the served calls kvpb.Cache/Put and /Get
(ISSUE 33): a dict, numpy over Python-sized integers, one call at a time.

The pool has `sessions` slots. `put(session, layer, data)` gives a session
that has none the lowest slot never used, or, when every slot is taken, the
slot of the session that was given its own longest ago, which is evicted
whole: all its layers. It keeps `data` under (session, layer) and returns
(word, admitted): word = the wraparound sum over j of x[j] * (2j + 1) with
`data` read as little-endian uint32 words x, admitted = how many sessions
had been given a slot before this call's session was given its own. `get(session, layer)` returns the
bytes of the newest put, or raises NotFound for a session that was never
put or has been evicted, or a layer of it that was not put since it was
given its slot: never other bytes.

No jax, no ring, no lane, no chunks, and nothing of the served path
(`kv_service`, `device_path`, the native library) is imported here.
"""
import numpy as np


class NotFound(KeyError):
    """No such (session, layer) in the pool."""


def check_put(nbytes: int, layer: int, layers: int, layer_bytes: int) -> None:
    if nbytes < 8 or nbytes % 8 or nbytes > layer_bytes:
        raise ValueError(f"a Put is a multiple of 8 bytes from 8 to "
                         f"{layer_bytes}, not {nbytes}")
    if not 0 <= layer < layers:
        raise ValueError(f"layer {layer} of {layers}")


def word(data) -> int:
    """The integrity word a Put of `data` must be acknowledged with."""
    x = np.frombuffer(bytes(data), dtype="<u4")
    mult = np.arange(x.size, dtype=np.uint64) * np.uint64(2) + np.uint64(1)
    prod = (x.astype(np.uint64) * mult) & np.uint64(0xFFFFFFFF)
    return int(prod.sum(dtype=np.uint64)) & 0xFFFFFFFF


class Cache:
    def __init__(self, sessions: int, layers: int, layer_bytes: int):
        self.sessions, self.layers = sessions, layers
        self.layer_bytes = layer_bytes
        self.admitted = 0  # sessions given a slot so far
        self.slots = {}    # session -> (slot, admitted), oldest first
        self.kept = {}     # (session, layer) -> bytes

    def put(self, session: int, layer: int, data) -> tuple:
        data = bytes(data)
        check_put(len(data), layer, self.layers, self.layer_bytes)
        if session not in self.slots:
            slot = len(self.slots)
            if slot == self.sessions:
                oldest = next(iter(self.slots))
                slot, _ = self.slots.pop(oldest)
                for key in [k for k in self.kept if k[0] == oldest]:
                    del self.kept[key]
            self.slots[session] = slot, self.admitted
            self.admitted += 1
        self.kept[session, layer] = data
        return word(data), self.slots[session][1]

    def get(self, session: int, layer: int) -> bytes:
        try:
            return self.kept[session, layer]
        except KeyError:
            raise NotFound((session, layer)) from None
