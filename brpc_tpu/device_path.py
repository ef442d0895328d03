"""Device data path: framed payloads stream host -> HBM -> host through
a pipelined DMA staging ring.

PR-8 (ISSUE 9) rebuilt this module around `DeviceStagingRing`
(cpp/tici/block_pool.cc, exported through cpp/trpc/c_api.cc): the
payload is cut into chunks, each chunk staged into a depth-N ring of
registered pool slots by ONE pass over its bytes that also computes
their crc32c (brpc_tpu/native.copy_crc32c; ISSUE 30) and framed IN
PLACE by the C++ framework from that crc (header + meta written right
before the payload — no payload memcpy and no second pass over it,
brpc_tpu/native.frame_in_place), so that H2D of chunk i+1, the
on-device integrity kernel on chunk i, and D2H + crc32c verification of
chunk i-1 overlap. That is the transport seam the reference's RDMA
endpoint implements with ibv_post_send out of its registered block pool
(rdma_endpoint.cpp CutFromIOBufList): device DMA reading straight from
pool-registered frame bytes, several transfers in flight.

Three threads drive a `DeviceLane` (ISSUE 32), as that endpoint's sender,
its doorbell and its completion-queue poller do: the thread that calls
`submit` stages a chunk and puts it on the chip (acquire, the one pass over
its bytes, frame, H2D), the lane's dispatch thread steps it (the jitted call
and the request for both results' copies back), the lane's completion
thread retires it (wait for the D2H, crc32c, complete) -- each in the order
of the submits, so the pass over chunk i+1's bytes and its H2D run beside
chunk i's dispatch. The lane is long-lived and takes independent requests:
`_ChunkPipeline.run` is a loop over `submit` on a lane that lives inside
that call, and the served handler (brpc_tpu/tensor_service.py) submits each
call to one that lives as long as the server.

`run(..., device=d)` is the smoke's one verified ring pass over any one of
`jax.devices()` (chip_smoke.py walks them all from one process); the
measurement is `benchmark/run.py --workload bulk_64m_ring`.
"""
import queue
import threading
import time
from functools import lru_cache

import numpy as np

# In-place frame headroom per slot (importing brpc_tpu.native does NOT
# load the shared library — that happens lazily at the first call).
from brpc_tpu import native, spans
from brpc_tpu.native import IN_PLACE_HEADROOM as HEADROOM

# Never park forever on the ring (ISSUE 10c): 30s >> any sane per-chunk
# latency. Covers the credit and the slot.
ACQUIRE_TIMEOUT_US = 30_000_000


def _integrity_word(words):
    """Order-sensitive integrity word over uint32 words: a weighted
    wraparound sum (odd per-position multipliers, so swapping any two
    distinct words changes the result). Unlike the adler scan used by
    collective_echo, this is ONE fused multiply-reduce pass — it maps to
    vector units instead of a sequential cumsum, keeping the on-device
    integrity check off the pipeline's critical path."""
    import jax.numpy as jnp

    idx = jnp.arange(words.shape[-1], dtype=jnp.uint32)
    return jnp.sum(words * (idx * jnp.uint32(2) + jnp.uint32(1)),
                   dtype=jnp.uint32)


def _integrity_word_host(words: np.ndarray) -> int:
    """The same word computed by plain numpy on the host — the reference
    the on-device kernel is held to (uint32 products and sum wrap)."""
    idx = np.arange(words.shape[-1], dtype=np.uint32)
    return int(np.sum(words * (idx * np.uint32(2) + np.uint32(1)),
                      dtype=np.uint32))


def _resolve_device(device):
    """The device to drive; never a CPU standing in for a chip.

    The cpu platform is accepted only where the process was pinned to it
    on purpose (`JAX_PLATFORMS=cpu`: the tests, this sandbox). With any
    other platform list — or none, where jax falls back to the CPU when
    it finds no accelerator — a cpu device is an error, so a chip run can
    never put a CPU's number under a device key."""
    import jax

    dev = jax.devices()[0] if device is None else device
    if dev.platform == "cpu" and jax.config.jax_platforms != "cpu":
        raise RuntimeError(
            f"device path would run on {dev} but this process is not "
            f"pinned to the cpu (jax_platforms={jax.config.jax_platforms!r}"
            f", default backend {jax.default_backend()!r}): no accelerator "
            "was found, or a cpu device was passed in a chip run")
    return dev


@lru_cache(maxsize=4)
def _touch_kernel(chunk_words: int, platform: str):
    """Persistent jitted integrity kernel over one chunk: returns the
    chunk (identity) and its integrity word — proves on-device compute
    READ the bytes, not just DMA'd them through. Donation lets XLA reuse
    the input buffer on real devices (no per-chunk allocation); the CPU
    backend ignores donation, so it is only requested off-cpu."""
    import jax

    def touch(x):
        return x, _integrity_word(x)

    if platform == "cpu":
        return jax.jit(touch)
    return jax.jit(touch, donate_argnums=0)


@lru_cache(maxsize=4)
def _tensor_step_kernel(key: int, platform: str):
    """`_touch_kernel`'s sibling for the served call tensor.Step (ISSUE 29;
    brpc_tpu/tensor_service.py): over the request as uint32 words x,
    returns (y, w) with y[j] = x[j] ^ key for j >= 2, y[0], y[1] = x[0],
    x[1] (caller and sequence number stay readable), and w the integrity
    word of x -- both made on the device by this one jitted function from
    the bytes that were DMA'd there. Not the identity, so an answer that
    never crossed the chip cannot compare equal. Traced as module
    `jit_tensor_step`; it compiles once a shape, so the service calls it
    with a few fixed sizes only (tensor_service.buckets). Donation as
    `_touch_kernel`."""
    import jax
    import jax.numpy as jnp

    def tensor_step(x):
        idx = jnp.arange(x.shape[-1], dtype=jnp.uint32)
        y = jnp.where(idx >= 2, x ^ jnp.uint32(key), x)
        return y, _integrity_word(x)

    if platform == "cpu":
        return jax.jit(tensor_step)
    return jax.jit(tensor_step, donate_argnums=0)


@lru_cache(maxsize=4)
def _kv_put_kernel(platform: str):
    """The step of the served call kvpb.Cache/Put (ISSUE 33;
    brpc_tpu/kv_service.py): a kernel that carries device state. Over one
    layer's pool buffer `pool` (uint32[sessions * row_words], the sessions'
    rows end to end -- flat, so that a chunk is one contiguous run of it
    and no row is padded to a tile; donated: XLA writes in place, the pool
    is never copied), one chunk `x` of a call as uint32 words and `where` =
    int32[2] (the chunk's first word in the pool, and in the call), returns
    (pool', w): pool' is pool with x written at [at:at + len(x)], and w =
    the wraparound sum of x[j] * (2 (j + off) + 1) READ BACK FROM pool'
    after the write -- the chunk's share of the call's integrity word,
    which therefore does not depend on how the call was cut. Nothing of the
    chunk comes back to the host but w. Traced as module `jit_kv_put_step`;
    one shape, so it compiles once. Donation as `_touch_kernel`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def kv_put_step(pool, x, where):
        at, off = where[0], where[1]
        pool = lax.dynamic_update_slice(pool, x, (at,))
        landed = lax.dynamic_slice(pool, (at,), x.shape)
        idx = jnp.arange(x.shape[0], dtype=jnp.uint32) + off.astype(jnp.uint32)
        return pool, jnp.sum(landed * (idx * jnp.uint32(2) + jnp.uint32(1)),
                             dtype=jnp.uint32)

    if platform == "cpu":
        return jax.jit(kv_put_step)
    return jax.jit(kv_put_step, donate_argnums=0)


@lru_cache(maxsize=4)
def _kv_get_kernel(chunk_words: int):
    """kvpb.Cache/Get's read of one chunk out of a layer's pool buffer:
    pool[at:at + chunk_words] with `where` as `_kv_put_kernel`'s. The pool
    is not donated: it stays."""
    import jax
    from jax import lax

    def kv_get_step(pool, where):
        return lax.dynamic_slice(pool, (where[0],), (chunk_words,))

    return jax.jit(kv_get_step)


def _h2d(view: np.ndarray, dev):
    """Import one staged slot view onto the device: dlpack zero-copy on
    host-backed platforms (the registered slot IS the device buffer), a
    real H2D DMA otherwise."""
    import jax

    if dev.platform == "cpu":
        try:
            return jax.dlpack.from_dlpack(view)
        except Exception:
            pass
    return jax.device_put(view, dev)


class DeviceLane:
    """A long-lived lane through the staging ring: independent requests
    are submitted one at a time and each is answered when its D2H is back.

    `submit(fill, nbytes, token)` runs on the caller's thread (the
    submitter): a credit and a ring slot; `fill(view)` stages `nbytes`
    into the slot and returns their crc32c, both from one pass over the
    bytes (native.copy_crc32c, ParkedCall.copy_into: there is no other
    kind of fill, and one that returns nothing is a TypeError); the C++
    framer writes header + meta around that crc and never reads the
    payload; H2D. The chunk then goes to the lane's dispatch thread: the
    jitted `kernel(x) -> (y, word)` and the request for both results'
    copies back -- beside the submitter's pass over the next chunk's bytes
    and its H2D, which need nothing of it (ISSUE 32). From there it goes
    to the lane's completion thread, which waits for the D2H, checks crc32c
    of the returned bytes against the frame's where `verify` is set (a
    kernel that is the identity), calls
    `on_done(token, host_bytes, word, good)` and completes the slot.
    One submitter, one FIFO, one dispatcher, one FIFO, one retirer: all in
    the order of the submits. `host_bytes` is the device's answer on the
    host, not the slot. That is the RDMA endpoint's sender, its doorbell
    and its completion-queue poller. `close()` drains what is in flight
    and joins both threads; a lane lives through any number of submits
    before it.

    The kernel's contract (ISSUE 33): `kernel(x) -> (y, word)`, called on
    the dispatch thread, in submit order, and by nothing else. `word` is a
    device scalar and always comes back. `y` is the chunk's bulk result,
    and comes back as `host_bytes`; a kernel whose result STAYS on the
    device returns `y` None, and then only the 4-byte word crosses back
    (`host_bytes` None). A chunk may bring a kernel of its own
    (`submit(..., kernel=)`): a closure over device state that it replaces
    (`(pool, x, where) -> (pool', word)` with the pool donated) is safe
    there because one thread runs every step, in order. One token may own
    several submits (a call cut into chunks): `on_done` and `on_abandon`
    run once a chunk, in submit order, and joining them is the owner's.

    Where the cut lies, and why (PERF.md section 6, PR 32): the H2D stays
    with the submitter because `device_put` copies the slot's bytes on the
    thread that calls it, and they are in that core's cache from the fill;
    on the dispatch thread it cost a fifth more, and left that thread with
    three quarters of a chunk's work. The three threads share one
    interpreter lock, so what they hand over is kept short: the credits
    are tokens in a C queue, and the calls of a microsecond keep the lock
    (native._short_calls).

    At depth 1 there is no helper thread: nothing is in flight that they
    could overlap, so `submit` dispatches and retires its chunk before it
    returns.

    An error on any thread aborts the ring: the submitter's leaves
    `submit` as itself; a helper thread's is kept in `failure` (the first
    one, whichever thread met it), the chunk it met and every chunk behind
    it reach the completion thread marked abandoned and are given to
    `on_abandon(token)` instead of `on_done`, after every earlier chunk's
    `on_done`, and the next `submit` raises RingAbortedError. Never
    parked forever (ISSUE 10c): an acquire that outlasts
    ACQUIRE_TIMEOUT_US aborts the ring too.

    Spans (brpc_tpu/spans.py), request = token: per submit one
    `ring.launch` on the submitter with children ring.acquire (waiting for
    a credit and a free slot), whatever `fill` opens around its pass over
    the bytes (ring.stage in the ring pass, tensor.fill in a served call:
    the copy into the slot AND the crc32c), ring.frame (header + meta: a
    few microseconds), ring.h2d; one `ring.dispatch` with the child
    ring.kernel_dispatch (the jitted function + the async D2H requests);
    and one `ring.retire` with children ring.d2h_wait (blocks until the
    device is done), ring.verify (crc32c, where `verify`), ring.complete.
    `ring.dispatch` is top-level on the dispatch thread, `ring.retire` on
    the completion thread. Self times are per thread. PERF.md section 3
    names the metric that reads each."""

    def __init__(self, ring, dev, kernel, depth, on_done, *, verify=True,
                 on_abandon=None):
        self.ring = ring
        self.dev = dev
        self.kernel = kernel
        self.on_done = on_done
        self.on_abandon = on_abandon
        self.verify = verify
        self.failure = None  # the helper threads' first error
        self._failing = threading.Lock()
        # `depth` less what is past `ring.acquire`, whatever thread holds
        # it and whatever the ring's own depth: it bounds both queues. A
        # token a credit, in a C queue: taking and giving one runs no
        # Python, where threading.Semaphore is a condition variable in it.
        self._credits = queue.SimpleQueue()
        for _ in range(depth):
            self._credits.put(None)
        self._staged = queue.SimpleQueue()   # submitter -> dispatch thread
        self._handoff = queue.SimpleQueue()  # dispatch -> completion thread
        self._helpers = []
        if depth > 1:
            self._helpers = [
                threading.Thread(target=self._dispatch_staged,
                                 name="ring.dispatch"),
                threading.Thread(target=self._retire_handed_over,
                                 name="ring.completions")]
            for helper in self._helpers:
                helper.start()

    def _acquire(self):
        """A credit, then the ring's next slot; both within the timeout."""
        try:
            self._credits.get(timeout=ACQUIRE_TIMEOUT_US / 1e6)
        except queue.Empty:
            pass
        else:
            try:
                return self.ring.acquire(ACQUIRE_TIMEOUT_US)
            except TimeoutError:
                pass
            except BaseException:
                self._credits.put(None)  # an aborted ring: nothing launched
                raise
        self.ring.abort()
        raise RuntimeError(
            "staging-ring acquire timed out (lost completion or wedged "
            "device stream); ring aborted")

    def submit(self, fill, nbytes, token, correlation_id=1, kernel=None):
        try:
            with spans.span("ring.launch", token):
                with spans.span("ring.acquire", token):
                    slot = self._acquire()
                sa = self.ring.slots[slot]
                view = sa[HEADROOM:HEADROOM + nbytes]
                # Staged once, by the one pass over the bytes that also
                # gives their crc32c (ISSUE 30); framed in place from it
                # (header + meta only: no payload memcpy -- ISSUE 9
                # satellite -- and no second pass); imported zero-copy
                # where the platform backs arrays with host memory.
                crc = fill(view)
                with spans.span("ring.frame", token):
                    native.frame_in_place(correlation_id, sa, HEADROOM,
                                          nbytes, crc)
                with spans.span("ring.h2d", token):
                    x = _h2d(view.view(np.uint32), self.dev)
            staged = (token, slot, crc, x, kernel or self.kernel)
            if self._helpers:
                self._staged.put(staged)
                return
            item = self._dispatch(staged)
        except BaseException:
            self.ring.abort()  # a launch that failed never frees its slot
            raise
        self._retire(item)

    def _dispatch(self, staged):
        token, slot, crc, x, kernel = staged
        with spans.span("ring.dispatch", token), \
                spans.span("ring.kernel_dispatch", token):
            y, word = kernel(x)
            # Everything `_retire` will wait for is asked for here.
            for out in (y, word):
                if hasattr(out, "copy_to_host_async"):
                    out.copy_to_host_async()
        return token, slot, crc, y, word

    def _retire(self, item):
        token, slot, crc, y, word = item
        with spans.span("ring.retire", token):
            with spans.span("ring.d2h_wait", token):
                # Blocks until the device is done; then its word comes back,
                # and the bulk result where the kernel gave one.
                back = None if y is None else np.asarray(y)
                word = int(word)
            good = True
            if self.verify:
                with spans.span("ring.verify", token):
                    # The D2H buffer against the crc32c the C++ framework
                    # embedded at frame time: per-chunk integrity with no
                    # copy-back and no re-parse.
                    good = native.crc32c(back) == crc
            self.on_done(token, back, word, good)
            with spans.span("ring.complete", token):
                self.ring.complete(slot)
        self._credits.put(None)

    def _fail(self, error):
        """A helper thread's error: the first is kept, and the ring is
        aborted, which is what unblocks a submitter parked in `_acquire`."""
        with self._failing:
            if self.failure is None:
                self.failure = error
        self.ring.abort()

    def _dispatch_staged(self):
        """The dispatch thread's body: step what the submitter staged and
        put on the chip, in that order, until its `None`, and hand each
        chunk on with whether it was. After an error, its own or the
        completion thread's, nothing more is dispatched: a chunk goes on as
        it came, for the completion thread to abandon in its turn."""
        for staged in iter(self._staged.get, None):
            if self.failure is None:
                try:
                    self._handoff.put((self._dispatch(staged), True))
                    continue
                except BaseException as e:
                    self._fail(e)
            self._handoff.put((staged, False))
        self._handoff.put(None)

    def _retire_handed_over(self):
        """The completion thread's body: retire what the dispatch thread
        hands over, in that order, until its `None`. From the first chunk
        that fails here or comes undispatched, each is abandoned."""
        retiring = True
        for item, dispatched in iter(self._handoff.get, None):
            if retiring and dispatched:
                try:
                    self._retire(item)
                    continue
                except BaseException as e:
                    self._fail(e)
            retiring = False
            # The failed chunk's credit and each one's behind it: the ring
            # is aborted, so the submitter that takes one meets that at
            # once and never waits the timeout out for a credit.
            self._credits.put(None)
            if self.on_abandon is not None:
                self.on_abandon(item[0])

    def close(self):
        """Everything submitted is retired (or abandoned) and both helper
        threads are gone when this returns; `failure` says how."""
        if self._helpers:
            self._staged.put(None)  # the dispatch thread sends it on
            for helper in self._helpers:
                helper.join()
            self._helpers = []


class _ChunkPipeline:
    """Drives the staging ring at a given depth over a fixed list of
    chunks, pass after pass: a loop over `DeviceLane.submit` (payload
    staged into the registered slot by the pass that computes its crc32c,
    framed IN PLACE, dlpack zero-copy import where the platform backs
    arrays with host memory, donated device buffers elsewhere, depth-N
    chunks in flight so H2D/compute/D2H of neighboring chunks overlap) --
    the same lane a served handler submits to (brpc_tpu/tensor_service.py).

    Who runs where: `run()`'s caller stages every chunk and puts it on the
    device. At depth 1 it also dispatches and retires each chunk before
    the next. At any other
    depth the lane's dispatch thread calls `touch`, in launch order, and
    its completion thread retires the chunks in launch order, so
    `dev_checks` is in launch order; both are started and joined inside
    `run()`. A credit bounds the chunks staged and not yet completed to
    `depth` whatever the ring's own depth. An error on any thread aborts
    the ring and leaves `run()` as itself, after the other threads have
    stopped.

    Spans, request = (pass, chunk): per pass one `ring.pass` (its self
    time is this loop's own), the lane's per chunk (DeviceLane), and the
    caller's wait for the last dispatches and retires, `ring.drain`, under
    the last `ring.pass`."""

    def __init__(self, ring, chunks, dev, touch, depth, copy_mode=False):
        if copy_mode:
            # The per-copy loop went with ISSUE 31; the parameter waits for
            # benchmark/drivers/ring.py to stop passing it (ROADMAP C14).
            raise ValueError("copy_mode: the per-copy loop is gone "
                             "(ISSUE 31); there is one ring path")
        self.ring = ring
        self.chunks = chunks          # list of uint32 chunk arrays
        self.dev = dev
        self.touch = touch
        self.depth = depth
        self.chunk_bytes = chunks[0].nbytes
        self.ok = True
        self.dev_checks = []
        self.passes = 0  # passes begun: the `pass` of a span's request

    def _retired(self, token, back, word, good):
        self.ok = self.ok and good
        self.dev_checks.append(word)

    def _submit(self, lane, k):
        req = (self.passes, k)

        def stage(view):
            with spans.span("ring.stage", req):
                return native.copy_crc32c(view, self.chunks[k])

        lane.submit(stage, self.chunk_bytes, req, k + 1)

    def run(self, reps):
        """`reps` passes over the chunks; every chunk launched here is
        retired when this returns. Seconds taken."""
        t0 = time.monotonic()
        lane = None
        try:
            for _ in range(reps):
                self.passes += 1
                # The pass's span ends with the pass's last launch; the
                # chunks still in flight then retire beside the next
                # pass's launches, or during the drain below.
                with spans.span("ring.pass", (self.passes, None)):
                    if lane is None:
                        # Under the first pass's span, so that the
                        # launcher's self times cover all of its time.
                        lane = DeviceLane(self.ring, self.dev, self.touch,
                                          self.depth, self._retired)
                    for k in range(len(self.chunks)):
                        self._submit(lane, k)
        except BaseException:
            self.ring.abort()  # a launch that failed never frees its slot
            raise
        finally:
            if lane is not None:
                if self.depth > 1:
                    req = (self.passes, None)
                    with spans.span("ring.pass", req), \
                            spans.span("ring.drain", req):
                        lane.close()
                if lane.failure is not None:
                    # What this thread met after that (RingAbortedError
                    # out of `_acquire`) is only its echo.
                    raise lane.failure
        return time.monotonic() - t0


def run(payload_mb: int = 4, reps: int = 5, ring_depth: int = 4,
        chunk_kb: int = 2044, device=None) -> dict:
    """The smoke's ring pass: `payload_mb` in chunks of `chunk_kb` through
    one depth-`ring_depth` ring, a warm-up pass and `reps` timed ones,
    every chunk's crc32c verdict and every on-device word held to the
    host's. GB/s counts a verified byte once, as benchmark/stats.gbps."""
    from brpc_tpu import compile_cache

    compile_cache.enable()
    dev = _resolve_device(device)
    chunk_bytes = (chunk_kb << 10) & ~4095
    n_chunks = max(1, (payload_mb << 20) // chunk_bytes)
    payload = np.arange(n_chunks * chunk_bytes // 4, dtype=np.uint32)
    chunks = np.split(payload, n_chunks)
    touch = _touch_kernel(chunk_bytes // 4, dev.platform)
    ring = native.DeviceStagingRing(ring_depth, chunk_bytes + 1024)
    try:
        pipe = _ChunkPipeline(ring, chunks, dev, touch, ring_depth)
        pipe.run(1)  # warm-up: compiles, first transfers
        pipe.dev_checks.clear()
        seconds = pipe.run(reps)
        highwater = ring.inflight_highwater
        registered = ring.registered
    finally:
        ring.close()
    want = [_integrity_word_host(c) for c in chunks] * reps
    return {
        "device_path_gbps": round(payload.nbytes * reps / seconds / 1e9, 3),
        "device_path_ok": bool(pipe.ok and pipe.dev_checks == want),
        "device_path_ring_depth": ring_depth,
        "device_path_chunk_bytes": chunk_bytes,
        "device_path_inflight_highwater": int(highwater),
        "device_path_registered_staging": bool(registered),
        "device_path_device": f"{dev.platform}:{dev.device_kind}",
    }
