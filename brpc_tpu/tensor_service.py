"""tensor.Step served from the process that holds the chip (ISSUE 29,
ROADMAP A1): the payload crosses the chip inside the RPC, not beside it.

`serve(device, ...)` starts the C API's pull server (a `Server` inside this
process: tensorpb.Tensor/Step over TCP and the shm link, builtin portal on
the same port; cpp/trpc/c_api.h) and joins it to a long-lived
`device_path.DeviceLane`:

    taker thread        take a parked call -> lane.submit: a ring slot,
    (the submitter)     the request attachment copied into it, its tail
                        zeroed and the crc32c of both computed in ONE pass
                        (ParkedCall.copy_into), header + meta written from
                        that crc, H2D
    lane's dispatch     jitted `tensor_step`, async D2H -- beside the taker's
    thread              pass over the next call's bytes and its H2D
                        (ISSUE 32)
    lane's completion   D2H back -> reply y ‖ w from the returned host
    thread              buffer (one copy into the reply) -> slot completed

The C++ handler only stamps and parks the call, so no fiber worker runs
Python or waits for the interpreter lock; `take` blocks in C++ with the
lock released. The answer is `brpc_tpu.tensor_reference.step`'s, made on
the device. A request over `max_bytes`, not a multiple of 8 or under 16
bytes fails with TERR_REQUEST. The jitted step compiles once a shape, so a
request crosses the chip at the smallest of a few fixed sizes that holds it
(`buckets`: powers of two from 4 KiB, and `max_bytes`), its tail zeroed in
the slot -- zeros add nothing to w, and y's tail is not sent back -- and
`serve` compiles every one of them before the first call: no call ever
waits for the compiler. Ring aborted or device error (on the dispatch or
the completion thread: the lane abandons the call it met and every call
behind it, `_abandoned`): every parked and in-flight call fails, `take`
returns, the taker and the lane's two threads end (the ISSUE 10c rule);
`close()` then only joins.

Spans (brpc_tpu/spans.py) beside the lane's `ring.*`: tensor.take (waiting
for a call, then taking it), tensor.fill (attachment -> slot, zero tail and
crc32c: the taker's one pass over a call's bytes), tensor.reply
(answer -> response attachment -> `done`). Stages and counters on the C++
side: tdev.take_wait, tdev.reply, rpc_tensor_* (c_api.h); rpc_tensor_calls
is counted here, where the D2H of a step's result has come back.
"""
import bisect
import threading

import numpy as np

from brpc_tpu import native, spans
from brpc_tpu.native import TERR_INTERNAL, TERR_REQUEST

TAKE_POLL_US = 50_000  # how often the taker looks at the ring's health
MIN_BUCKET = 4096


def buckets(max_bytes: int) -> list:
    """The sizes the step is compiled for and a request is padded to:
    powers of two from MIN_BUCKET to under `max_bytes`, and `max_bytes`."""
    sizes, size = [], MIN_BUCKET
    while size < max_bytes:
        sizes.append(size)
        size *= 2
    return sizes + [max_bytes]


class TensorService:
    """What `serve` returns: `port`, `close()`, and `failure` (the error
    that shut the service down by itself, if one did)."""

    def __init__(self, device, depth, max_bytes, key, port=0):
        from brpc_tpu import compile_cache, device_path

        compile_cache.enable()
        if max_bytes < 16 or max_bytes % 8:
            raise ValueError("max_bytes is a multiple of 8, 16 at least")
        self.max_bytes = max_bytes
        self.buckets = buckets(max_bytes)
        self.failure = None
        self.dev = device_path._resolve_device(device)
        # Slot = payload + frame headroom, as device_path.run sizes it;
        # made once, never per call.
        self.ring = native.DeviceStagingRing(depth, max_bytes + 1024)
        self.server = self.lane = self._taker = None
        try:
            step = device_path._tensor_step_kernel(key, self.dev.platform)
            for size in self.buckets:  # compiled now, not under a call
                step(device_path._h2d(np.zeros(size // 4, np.uint32),
                                      self.dev))[1].block_until_ready()
            self.server = native.PullServer(port)
            self.port = self.server.port
            self.lane = device_path.DeviceLane(
                self.ring, self.dev, step, depth, self._answered,
                verify=False, on_abandon=self._abandoned)
        except BaseException:
            self.close()
            raise
        self._taker = threading.Thread(target=self._take_and_submit,
                                       name="tensor.taker")
        self._taker.start()

    def _shut(self, error):
        """A fault of the ring or the device: nothing more is served."""
        if self.failure is None:
            self.failure = error
        self.server.close_queue(TERR_INTERNAL)

    def _take_and_submit(self):
        try:
            while True:
                with spans.span("tensor.take"):
                    call = self.server.take(TAKE_POLL_US)
                if call is None:
                    if self.ring.aborted:
                        self._shut(self.lane.failure
                                   or native.RingAbortedError(
                                       "ring aborted (poisoned)"))
                    continue
                n = call.nbytes
                if n < 16 or n % 8 or n > self.max_bytes:
                    call.fail(TERR_REQUEST,
                              f"a tensor.Step request is a multiple of 8 "
                              f"bytes from 16 to {self.max_bytes}, not {n}")
                    continue
                try:
                    self._submit(call, n)
                except Exception as e:  # ring aborted, the fill's or H2D's
                    call.fail(TERR_INTERNAL, f"device leg failed: {e!r}")
                    # A helper thread's error, where the aborted ring this
                    # submit met is only its echo.
                    self._shut(self.lane.failure or e)
        except native.ServerClosedError:
            pass

    def _submit(self, call, n):
        def fill(view):
            with spans.span("tensor.fill", call):
                return call.copy_into(view)  # zero tail and crc32c included

        size = self.buckets[bisect.bisect_left(self.buckets, n)]
        self.lane.submit(fill, size, call)

    def _answered(self, call, back, word, good):
        """The lane's completion thread: D2H is back for `call`."""
        with spans.span("tensor.reply", call):
            native.tensor_step_answered()
            call.reply(back.view(np.uint8)[:call.nbytes],
                       np.array([word], dtype="<u4").view(np.uint8))

    def _abandoned(self, call):
        """The lane's completion thread: a device error on either helper
        thread reached `call` or a call ahead of it."""
        call.fail(TERR_INTERNAL, f"device leg failed: {self.lane.failure!r}")
        self._shut(self.lane.failure)

    def close(self):
        """Parked calls fail, in-flight calls are answered, the taker and
        the lane's threads are joined, the server is stopped and the ring
        freed."""
        if self.server is not None:
            self.server.close_queue()
        if self._taker is not None:
            self._taker.join()
        if self.lane is not None:
            self.lane.close()
        if self.server is not None:
            self.server.stop()
        self.ring.close()


def serve(device=None, depth=4, max_bytes=1 << 20, key=0x5EED1E57,
          port=0) -> TensorService:
    """Start serving tensor.Step on `device` (default: the first)."""
    return TensorService(device, depth, max_bytes, key, port)

