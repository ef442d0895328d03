"""What every service served from the process that holds the chip shares
(ISSUE 29, 33): the C API's pull server (a `Server` inside this process;
cpp/trpc/c_api.h), one taker thread, and one long-lived
`device_path.DeviceLane` over one staging ring.

    taker thread        take a parked call -> `_serve(call)`: the service
    (the submitter)     checks it and submits it to the lane, as one chunk
                        or as several
    lane's dispatch     the jitted step of each chunk, in submit order
    thread
    lane's completion   `_landed(token, host_bytes, word, good)` a chunk,
    thread              where the service answers the call

The C++ handlers only stamp and park a call, so no fiber worker runs Python
or waits for the interpreter lock; `take` blocks in C++ with the lock
released. Ring aborted or device error (on the taker, the dispatch or the
completion thread: the lane abandons the chunk it met and every chunk behind
it, `_abandoned`): every parked and in-flight call fails, `take` returns,
the taker and the lane's two threads end (the ISSUE 10c rule); `close()`
then only joins. `tensor_service` and `kv_service` are the two services.
"""
import threading

from brpc_tpu import native, spans
from brpc_tpu.native import TERR_INTERNAL

TAKE_POLL_US = 50_000  # how often the taker looks at the ring's health


class LaneService:
    """`port`, `close()`, and `failure` (the error that shut the service
    down by itself, if one did). A subclass sets what its hooks need, then
    calls this `__init__`, which ends with the taker running: `_warm()`
    compiles every step before the first call and returns the lane's
    kernel, `_serve(call)` runs on the taker, `_landed` and `_call_of` on
    the lane's completion thread."""

    TAKE_SPAN = None  # the taker waiting for a call, then taking it

    def __init__(self, device, depth, chunk_bytes, port=0):
        from brpc_tpu import compile_cache, device_path

        compile_cache.enable()
        self.failure = None
        self.dev = device_path._resolve_device(device)
        # Slot = payload + frame headroom, as device_path.run sizes it;
        # made once, never per call.
        self.ring = native.DeviceStagingRing(depth, chunk_bytes + 1024)
        self.server = self.lane = self._taker = None
        try:
            kernel = self._warm()
            self.server = native.PullServer(port)
            self.port = self.server.port
            self.lane = device_path.DeviceLane(
                self.ring, self.dev, kernel, depth, self._landed,
                verify=False, on_abandon=self._abandoned)
        except BaseException:
            self.close()
            raise
        self._taker = threading.Thread(target=self._take_and_serve,
                                       name=self.TAKE_SPAN)
        self._taker.start()

    def _shut(self, error):
        """A fault of the ring or the device: nothing more is served."""
        if self.failure is None:
            self.failure = error
        self.server.close_queue(TERR_INTERNAL)

    def _take_and_serve(self):
        try:
            while True:
                with spans.span(self.TAKE_SPAN):
                    call = self.server.take(TAKE_POLL_US)
                if call is None:
                    if self.ring.aborted:
                        self._shut(self.lane.failure
                                   or native.RingAbortedError(
                                       "ring aborted (poisoned)"))
                    continue
                try:
                    self._serve(call)
                except Exception as e:  # ring aborted, the fill's or H2D's
                    call.fail(TERR_INTERNAL, f"device leg failed: {e!r}")
                    # A helper thread's error, where the aborted ring this
                    # submit met is only its echo.
                    self._shut(self.lane.failure or e)
        except native.ServerClosedError:
            pass

    def _abandoned(self, token):
        """The lane's completion thread: a device error on either helper
        thread reached this chunk or a chunk ahead of it. Its call fails,
        once however many of its chunks come here (`ParkedCall.fail` after
        an answer does nothing)."""
        self._call_of(token).fail(
            TERR_INTERNAL, f"device leg failed: {self.lane.failure!r}")
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
