"""tensor.Step served from the process that holds the chip (ISSUE 29,
ROADMAP A1): the payload crosses the chip inside the RPC, not beside it.

`serve(device, ...)` starts the C API's pull server (a `Server` inside this
process, which hosts tensorpb.Tensor/Step beside kvpb.Cache over TCP and
the shm link, builtin portal on the same port; cpp/trpc/c_api.h) and joins
it to a long-lived `device_path.DeviceLane` (brpc_tpu/lane_service.py has
the skeleton this shares with kv_service):

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

The answer is `brpc_tpu.tensor_reference.step`'s, made on the device. A
request over `max_bytes`, not a multiple of 8 or under 16 bytes fails with
TERR_REQUEST, a call of another service's method with TERR_NO_METHOD. The
jitted step compiles once a shape, so a request crosses the chip at the
smallest of a few fixed sizes that holds it (`buckets`: powers of two from
4 KiB, and `max_bytes`), its tail zeroed in the slot -- zeros add nothing
to w, and y's tail is not sent back -- and `serve` compiles every one of
them before the first call: no call ever waits for the compiler.

Spans (brpc_tpu/spans.py) beside the lane's `ring.*`: tensor.take (waiting
for a call, then taking it), tensor.fill (attachment -> slot, zero tail and
crc32c: the taker's one pass over a call's bytes), tensor.reply
(answer -> response attachment -> `done`). Stages and counters on the C++
side: tdev.take_wait, tdev.reply, rpc_tensor_* (c_api.h); rpc_tensor_calls
is counted here, where the D2H of a step's result has come back.
"""
import bisect

import numpy as np

from brpc_tpu import native, spans
from brpc_tpu.lane_service import LaneService
from brpc_tpu.native import TERR_NO_METHOD, TERR_REQUEST

MIN_BUCKET = 4096


def buckets(max_bytes: int) -> list:
    """The sizes the step is compiled for and a request is padded to:
    powers of two from MIN_BUCKET to under `max_bytes`, and `max_bytes`."""
    sizes, size = [], MIN_BUCKET
    while size < max_bytes:
        sizes.append(size)
        size *= 2
    return sizes + [max_bytes]


class TensorService(LaneService):
    """What `serve` returns: `port`, `close()`, and `failure` (the error
    that shut the service down by itself, if one did)."""

    TAKE_SPAN = "tensor.take"

    def __init__(self, device, depth, max_bytes, key, port=0):
        if max_bytes < 16 or max_bytes % 8:
            raise ValueError("max_bytes is a multiple of 8, 16 at least")
        self.max_bytes = max_bytes
        self.buckets = buckets(max_bytes)
        self.key = key
        super().__init__(device, depth, max_bytes, port)

    def _warm(self):
        from brpc_tpu import device_path

        step = device_path._tensor_step_kernel(self.key, self.dev.platform)
        for size in self.buckets:  # compiled now, not under a call
            step(device_path._h2d(np.zeros(size // 4, np.uint32),
                                  self.dev))[1].block_until_ready()
        return step

    def _serve(self, call):
        n = call.nbytes
        if call.method != native.STEP:
            call.fail(TERR_NO_METHOD, "this server serves tensor.Step")
            return
        if n < 16 or n % 8 or n > self.max_bytes:
            call.fail(TERR_REQUEST,
                      f"a tensor.Step request is a multiple of 8 "
                      f"bytes from 16 to {self.max_bytes}, not {n}")
            return

        def fill(view):
            with spans.span("tensor.fill", call):
                return call.copy_into(view)  # zero tail and crc32c included

        size = self.buckets[bisect.bisect_left(self.buckets, n)]
        self.lane.submit(fill, size, call)

    def _landed(self, call, back, word, good):
        """The lane's completion thread: D2H is back for `call`."""
        with spans.span("tensor.reply", call):
            native.tensor_step_answered()
            call.reply(back.view(np.uint8)[:call.nbytes],
                       np.array([word], dtype="<u4").view(np.uint8))

    def _call_of(self, call):
        return call


def serve(device=None, depth=4, max_bytes=1 << 20, key=0x5EED1E57,
          port=0) -> TensorService:
    """Start serving tensor.Step on `device` (default: the first)."""
    return TensorService(device, depth, max_bytes, key, port)

