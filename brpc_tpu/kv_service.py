"""kvpb.Cache served from the process that holds the chip (ISSUE 33, ROADMAP
D3): the prefill -> decode cache hand-off. A prefill node Puts one layer of
one prompt's cache a call; the bytes cross the staging lane as many chunks
and STAY in HBM, in a pool keyed by (session, layer), where a decode step
would read them. Get reads a layer back.

`serve(device, ...)` is `tensor_service.serve`'s sibling on the same
skeleton (brpc_tpu/lane_service.py: the C API's pull server, one taker, one
long-lived `device_path.DeviceLane`):

    taker thread        take a parked Put -> the session's pool slot (a new
    (the submitter)     session takes a free one, or evicts the oldest whole
                        session) -> for each chunk of the attachment: a ring
                        slot, the attachment FROM THE CHUNK'S OFFSET copied
                        into it with the crc32c of the pass
                        (ParkedCall.copy_into), header + meta, H2D
    lane's dispatch     jitted `kv_put_step` a chunk: the layer's pool
    thread              buffer, donated, is written in place at [slot,
                        offset] and the chunk's share of the word is read
                        back out of it; the new buffer takes the old one's
                        place
    lane's completion   a chunk's word is back (4 bytes: nothing else
    thread              crosses back) -> counted as landed; the call's last
                        one -> the layer is marked present, the Put is
                        answered with the sum of its chunks' words

The pool is `layers` device buffers of uint32[sessions * row], one a layer
(the sessions' rows end to end: flat, so no row is padded to a tile and a
chunk is one contiguous run), made when the service starts and never copied:
`layers` x `sessions` x `layer_bytes` of the chip's memory for the server's
life. A chunk is `chunk_bytes` (CHUNK_BYTES, 3 MiB, unless the caller says
otherwise: it fits one ring slot of the largest slab class), the last one of
a call zero-padded in the slot (zeros add nothing to the word; a row is a
whole number of chunks long, so the padding lands inside it), so the step
has one shape and compiles once, before the first call. The answer is
`brpc_tpu.kv_reference.Cache`'s: the word does not depend on how the call
was cut, a session keeps its slot until the pool is full and it is the
oldest, `Get` of what is not in the pool fails with KV_NOT_FOUND and never
gives other bytes (a layer is present from its Put's last word until its
session is evicted or the layer is put again). A Put that is not a multiple
of 8 bytes from 8 to `layer_bytes`, or names a layer the pool has not, fails
with TERR_REQUEST.

Get runs on the taker: a chunk at a time, `kv_get_step` reads [slot,
offset] out of the layer's buffer and the bytes come back (D2H); it takes
the lock the dispatch thread holds around a step, because a step replaces
the buffer it was given. It is right, and not yet fast.

Spans (brpc_tpu/spans.py) beside the lane's `ring.*`: kv.take (waiting for
a call, then taking it), kv.evict (the oldest session leaves the table),
kv.fill (a chunk's one pass: attachment -> slot, zero tail and crc32c),
kv.join (a call's first chunk submitted -> its last word back; it begins on
the taker and ends on the completion thread: `spans.record`), kv.reply
(the answer -> `done`). Stages and counters on the C++ side (c_api.h):
tdev.take_wait, tdev.reply; rpc_kv_puts, rpc_kv_gets, rpc_kv_failed;
rpc_kv_chunks and rpc_kv_bytes_landed are counted here, on the completion
thread, where a chunk's word came back from the device, and by nothing
else; rpc_kv_evictions, rpc_kv_pool_bytes, rpc_kv_resident_bytes (the
layers present, in bytes) where the table changes.
"""
import threading
import time

import numpy as np

from brpc_tpu import kv_reference, native, spans
from brpc_tpu.lane_service import LaneService
from brpc_tpu.native import KV_NOT_FOUND, TERR_NO_METHOD, TERR_REQUEST

# What a call is cut into: one ring slot's payload. The largest whole number
# of MiB that, with the frame headroom LaneService adds to a slot (1 KiB),
# fits the largest slab class (4 MiB, cpp/tici/block_pool.cc), so the ring's
# four slots stay one 16 MiB arena; a chunk's H2D call and dispatch cost the
# same at 1 and 3 MiB, so fewer, larger chunks are cheaper. A 9 MiB layer is
# 3 chunks, with no padding.
CHUNK_BYTES = 3 << 20


class _Session:
    """A session's tenancy of a pool slot."""

    __slots__ = ("slot", "admitted", "layers")

    def __init__(self, slot, admitted):
        self.slot = slot
        self.admitted = admitted  # sessions given a slot before this one
        self.layers = {}          # layer -> bytes present


class _Put:
    """A Put between the taker and its answer: the lane's token of every
    one of its chunks."""

    __slots__ = ("call", "tenancy", "chunks", "landed", "word", "t0")

    def __init__(self, call, tenancy, chunks):
        self.call = call
        self.tenancy = tenancy
        self.chunks = chunks  # what the call was cut into
        self.landed = 0       # of them, whose word is back
        self.word = 0
        self.t0 = time.monotonic()


class KvService(LaneService):
    """What `serve` returns: `port`, `close()`, and `failure` (the error
    that shut the service down by itself, if one did)."""

    TAKE_SPAN = "kv.take"

    def __init__(self, device, depth, layers, sessions, layer_bytes,
                 chunk_bytes, port=0):
        if min(layers, sessions) < 1 or layer_bytes < 8 or layer_bytes % 8 \
                or chunk_bytes < 8 or chunk_bytes % 8:
            raise ValueError("layers and sessions are 1 at least, "
                             "layer_bytes and chunk_bytes multiples of 8")
        self.layers, self.sessions = layers, sessions
        self.layer_bytes, self.chunk_bytes = layer_bytes, chunk_bytes
        self.row_chunks = -(-layer_bytes // chunk_bytes)
        self.pool_bytes = layers * sessions * self.row_chunks * chunk_bytes
        self.table = {}      # session -> _Session, oldest first
        self.admitted = 0    # sessions given a slot so far
        self.resident_bytes = 0
        self._table_lock = threading.Lock()  # taker and completion thread
        self._pool_lock = threading.Lock()   # dispatch thread and a Get
        super().__init__(device, depth, chunk_bytes, port)

    def _warm(self):
        """The pool, each chunk's `where`, and both steps compiled: all on
        the device before the first call."""
        import jax
        import jax.numpy as jnp

        from brpc_tpu import device_path

        words = self.chunk_bytes // 4
        row = self.row_chunks * words
        with jax.default_device(self.dev):
            self.pool = [jnp.zeros(self.sessions * row, jnp.uint32)
                         for _ in range(self.layers)]
        # [slot][k]: chunk k of a call's first word in the pool, in the call.
        self._where = [[jax.device_put(
            np.array([slot * row + k * words, k * words], np.int32), self.dev)
            for k in range(self.row_chunks)] for slot in range(self.sessions)]
        self._put_step = device_path._kv_put_kernel(self.dev.platform)
        self._get_step = device_path._kv_get_kernel(words)
        zeros = device_path._h2d(np.zeros(words, np.uint32), self.dev)
        self._put_chunk(0, self._where[0][0], zeros)[1].block_until_ready()
        self._get_step(self.pool[0],
                       self._where[0][0]).block_until_ready()
        native.kv_pool_state(self.pool_bytes, 0)
        return None  # every chunk brings its own step

    # ------------------------------------------------------- the taker

    def _serve(self, call):
        if call.method == native.PUT:
            self._put(call)
        elif call.method == native.GET:
            self._get(call)
        else:
            call.fail(TERR_NO_METHOD, "this server serves kvpb.Cache")

    def _admit(self, session):
        """The session's tenancy, given now to a session that has none: a
        slot never used, or the oldest session's, which is evicted whole."""
        tenancy = self.table.get(session)
        if tenancy is None:
            slot, evicted = len(self.table), 0
            if slot == self.sessions:
                with spans.span("kv.evict", session):
                    oldest = self.table.pop(next(iter(self.table)))
                    slot, evicted = oldest.slot, 1
                    self.resident_bytes -= sum(oldest.layers.values())
            tenancy = self.table[session] = _Session(slot, self.admitted)
            self.admitted += 1
            native.kv_pool_state(self.pool_bytes, self.resident_bytes,
                                 evicted)
        return tenancy

    def _put(self, call):
        n, layer = call.nbytes, call.layer
        try:
            kv_reference.check_put(n, layer, self.layers, self.layer_bytes)
        except ValueError as e:
            call.fail(TERR_REQUEST, str(e))
            return
        with self._table_lock:
            tenancy = self._admit(call.session)
            # Not present while it is being written.
            self.resident_bytes -= tenancy.layers.pop(layer, 0)
        put = _Put(call, tenancy, -(-n // self.chunk_bytes))
        for k in range(put.chunks):
            self._submit_chunk(put, layer, k)

    def _submit_chunk(self, put, layer, k):
        where = self._where[put.tenancy.slot][k]

        def fill(view):
            with spans.span("kv.fill", put):
                # Zero tail of the call's last chunk and crc32c included.
                return put.call.copy_into(view, k * self.chunk_bytes)

        def step(x):
            return self._put_chunk(layer, where, x)

        self.lane.submit(fill, self.chunk_bytes, put, k + 1, kernel=step)

    def _get(self, call):
        with self._table_lock:
            tenancy = self.table.get(call.session)
            n = tenancy.layers.get(call.layer) if tenancy else None
        if n is None:
            call.fail(KV_NOT_FOUND, f"session {call.session} layer "
                                    f"{call.layer} is not in the pool")
            return
        wheres = self._where[tenancy.slot][:-(-n // self.chunk_bytes)]
        with self._pool_lock:  # every read asked for before any is waited for
            chunks = [self._get_step(self.pool[call.layer], where)
                      for where in wheres]
        call.reply(np.concatenate([np.asarray(y) for y in chunks])
                   .view(np.uint8)[:n])

    # --------------------------------------------- the dispatch thread

    def _put_chunk(self, layer, where, x):
        """One chunk into the layer's buffer, which the step replaces: the
        lane's dispatch thread runs every one, in submit order. Nothing of
        the chunk is asked back but the word."""
        with self._pool_lock:
            self.pool[layer], word = self._put_step(self.pool[layer], x,
                                                    where)
        return None, word

    # ------------------------------------------- the completion thread

    def _landed(self, put, back, word, good):
        """A chunk's word is back from the device; the call's last one
        answers it."""
        call = put.call
        native.kv_chunk_landed(min(
            self.chunk_bytes, call.nbytes - put.landed * self.chunk_bytes))
        put.landed += 1
        put.word = (put.word + word) & 0xFFFFFFFF
        if put.landed < put.chunks:
            return
        spans.record("kv.join", put.t0, time.monotonic(), put)
        with spans.span("kv.reply", put):
            with self._table_lock:
                # Present from now on, if the session still has its slot.
                if self.table.get(call.session) is put.tenancy:
                    self.resident_bytes += (
                        call.nbytes - put.tenancy.layers.get(call.layer, 0))
                    put.tenancy.layers[call.layer] = call.nbytes
                    native.kv_pool_state(self.pool_bytes,
                                         self.resident_bytes)
            call.reply_put(put.word, put.tenancy.admitted)

    def _call_of(self, put):
        return put.call


def serve(device=None, *, layers, sessions, layer_bytes, depth=4,
          chunk_bytes=CHUNK_BYTES, port=0) -> KvService:
    """Start serving kvpb.Cache on `device` (default: the first) out of a
    pool of `layers` x `sessions` x `layer_bytes` of its memory."""
    return KvService(device, depth, layers, sessions, layer_bytes,
                     chunk_bytes, port)
