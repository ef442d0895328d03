"""ctypes bridge to the C++ framework (libtpurpc.so).

This is how the JAX side drives the FRAMEWORK's own code — tpu_std
framing (cpp/trpc/policy_tpu_std.cc), crc32c (cpp/tbase/crc32c.cc), and
registered-memory staging buffers (cpp/tici/block_pool.cc) — instead of a
Python re-implementation. dryrun_multichip and the device-path benchmark
both route every payload through these entry points, so a C++ framing or
checksum regression fails the multi-chip validation.

Reference parity: the RDMA build's block_pool.h hands registered memory
to the transport; here the same pool stages bytes that jax.device_put
DMAs to HBM.
"""
from __future__ import annotations

import ctypes
import errno
import re
import shutil
import subprocess
from pathlib import Path

import numpy as np

_REPO = Path(__file__).resolve().parent.parent
_LIB = None
_SHORT = None


def build(repo: Path = _REPO, timeout: float | None = None) -> Path:
    """cmake -G Ninja + ninja into `<repo>/build`; returns that directory.

    The one build rule for chip_smoke.py, the benchmark and the tests: a
    `build/` that was not configured for THIS checkout (its
    CMAKE_HOME_DIRECTORY names another path — a copied tree, which is what
    the chip tool makes — or it has no cache at all) is discarded and
    reconfigured, never trusted. Generated `*.pb.{h,cc}` come from this
    build's protoc run only. Raises RuntimeError with the tool's last
    output on failure."""
    build_dir = repo / "build"
    if build_dir.exists():
        try:
            m = re.search(r"^CMAKE_HOME_DIRECTORY:INTERNAL=(.*)$",
                          (build_dir / "CMakeCache.txt").read_text(), re.M)
        except FileNotFoundError:
            m = None
        if m is None or Path(m.group(1)).resolve() != repo.resolve():
            shutil.rmtree(build_dir)
    steps = []
    if not (build_dir / "build.ninja").exists():
        steps.append(["cmake", "-G", "Ninja", "-S", str(repo),
                      "-B", str(build_dir)])
    steps.append(["ninja", "-C", str(build_dir)])
    for cmd in steps:
        proc = subprocess.run(cmd, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"{' '.join(cmd)} failed (rc {proc.returncode}):\n"
                f"{proc.stdout[-4000:]}")
    return build_dir


def _short_calls() -> ctypes.PyDLL:
    """The same library for the calls that take about a microsecond and
    never wait (`frame_in_place`, a ring's `complete`, its `acquire` while a
    slot is free, the kv counters): through this handle a call keeps the
    interpreter lock.
    `lib()`'s calls give it up and take it back, which between the lane's
    three threads (brpc_tpu/device_path.py, ISSUE 32) is a hand-over to
    whichever thread waits for it, and a wait of tens of microseconds to get
    it back, for a microsecond of C (PERF.md section 6, PR 32). Nothing
    that copies, checksums or blocks goes through here."""
    global _SHORT
    if _SHORT is None:
        lib()  # the one check that the library is built
        L = ctypes.PyDLL(str(_REPO / "build" / "libtpurpc.so"))
        for name in ("tpurpc_ring_acquire", "tpurpc_ring_complete",
                     "tpurpc_frame_in_place", "tpurpc_kv_chunk_landed",
                     "tpurpc_kv_pool_state"):
            fn, declared = getattr(L, name), getattr(_LIB, name)
            fn.restype, fn.argtypes = declared.restype, declared.argtypes
        _SHORT = L
    return _SHORT


def lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is None:
        so = _REPO / "build" / "libtpurpc.so"
        if not so.exists():
            raise FileNotFoundError(
                f"{so} not built; run brpc_tpu.native.build() first"
            )
        L = ctypes.CDLL(str(so))
        L.tpurpc_global_init.restype = ctypes.c_int
        L.tpurpc_crc32c.restype = ctypes.c_uint32
        L.tpurpc_crc32c.argtypes = [ctypes.c_uint32, ctypes.c_void_p,
                                    ctypes.c_size_t]
        for fn in (L.tpurpc_crc32c_copy, L.tpurpc_crc32c_copy_tables):
            fn.restype = ctypes.c_uint32
            fn.argtypes = [ctypes.c_uint32, ctypes.c_void_p, ctypes.c_void_p,
                           ctypes.c_size_t]
        L.tpurpc_stage_fused_bytes.restype = ctypes.c_long
        L.tpurpc_block_alloc.restype = ctypes.c_void_p
        L.tpurpc_block_alloc.argtypes = [ctypes.c_size_t]
        L.tpurpc_block_free.argtypes = [ctypes.c_void_p]
        L.tpurpc_block_is_registered.restype = ctypes.c_int
        L.tpurpc_block_is_registered.argtypes = [ctypes.c_void_p]
        L.tpurpc_slab_allocated.restype = ctypes.c_long
        L.tpurpc_slab_recycled.restype = ctypes.c_long
        L.tpurpc_pool_id.restype = ctypes.c_uint64
        L.tpurpc_ring_create.restype = ctypes.c_void_p
        L.tpurpc_ring_create.argtypes = [ctypes.c_uint32, ctypes.c_size_t]
        L.tpurpc_ring_destroy.argtypes = [ctypes.c_void_p]
        L.tpurpc_ring_acquire.restype = ctypes.c_int
        L.tpurpc_ring_acquire.argtypes = [ctypes.c_void_p, ctypes.c_long]
        L.tpurpc_ring_complete.restype = ctypes.c_int
        L.tpurpc_ring_complete.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        L.tpurpc_ring_abort.argtypes = [ctypes.c_void_p]
        L.tpurpc_ring_aborted.restype = ctypes.c_int
        L.tpurpc_ring_aborted.argtypes = [ctypes.c_void_p]
        L.tpurpc_lease_pinned.restype = ctypes.c_uint64
        L.tpurpc_lease_reaped.restype = ctypes.c_uint64
        L.tpurpc_pool_epoch.restype = ctypes.c_uint64
        L.tpurpc_transport_tier_count.restype = ctypes.c_int
        L.tpurpc_transport_tier_name.restype = ctypes.c_long
        L.tpurpc_transport_tier_name.argtypes = [
            ctypes.c_int, ctypes.c_char_p, ctypes.c_size_t]
        L.tpurpc_transport_tier_descriptor_capable.restype = ctypes.c_int
        L.tpurpc_transport_tier_descriptor_capable.argtypes = [ctypes.c_int]
        L.tpurpc_transport_tier_zero_copy.restype = ctypes.c_int
        L.tpurpc_transport_tier_zero_copy.argtypes = [ctypes.c_int]
        L.tpurpc_transport_tier_cross_process.restype = ctypes.c_int
        L.tpurpc_transport_tier_cross_process.argtypes = [ctypes.c_int]
        L.tpurpc_transport_tier_ops.restype = ctypes.c_long
        L.tpurpc_transport_tier_ops.argtypes = [ctypes.c_int]
        L.tpurpc_transport_tier_one_sided.restype = ctypes.c_int
        L.tpurpc_transport_tier_one_sided.argtypes = [ctypes.c_int]
        L.tpurpc_transport_tier_sgl_max.restype = ctypes.c_long
        L.tpurpc_transport_tier_sgl_max.argtypes = [ctypes.c_int]
        for fn in ("posted", "completed", "bytes", "stale_rejects",
                   "cq_parks", "windows", "pending"):
            getattr(L, f"tpurpc_verbs_{fn}").restype = ctypes.c_long
        L.tpurpc_ring_slot.restype = ctypes.c_void_p
        L.tpurpc_ring_slot.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
        L.tpurpc_ring_slot_bytes.restype = ctypes.c_size_t
        L.tpurpc_ring_slot_bytes.argtypes = [ctypes.c_void_p]
        L.tpurpc_ring_depth.restype = ctypes.c_uint32
        L.tpurpc_ring_depth.argtypes = [ctypes.c_void_p]
        L.tpurpc_ring_registered.restype = ctypes.c_int
        L.tpurpc_ring_registered.argtypes = [ctypes.c_void_p]
        L.tpurpc_ring_inflight_highwater.restype = ctypes.c_uint64
        L.tpurpc_ring_inflight_highwater.argtypes = [ctypes.c_void_p]
        L.tpurpc_frame.restype = ctypes.c_long
        L.tpurpc_frame.argtypes = [ctypes.c_uint64, ctypes.c_void_p,
                                   ctypes.c_size_t, ctypes.c_void_p,
                                   ctypes.c_size_t]
        L.tpurpc_frame_in_place.restype = ctypes.c_long
        L.tpurpc_frame_in_place.argtypes = [
            ctypes.c_uint64, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        L.tpurpc_unframe.restype = ctypes.c_long
        L.tpurpc_unframe.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_size_t),
        ]
        L.tpurpc_stage_dump.restype = ctypes.c_long
        L.tpurpc_stage_dump.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        L.tpurpc_server_start.restype = ctypes.c_void_p
        L.tpurpc_server_start.argtypes = [ctypes.c_int]
        L.tpurpc_server_port.restype = ctypes.c_int
        L.tpurpc_server_port.argtypes = [ctypes.c_void_p]
        L.tpurpc_server_take.restype = ctypes.c_void_p
        L.tpurpc_server_take.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_uint64)]
        L.tpurpc_server_close_queue.argtypes = [ctypes.c_void_p,
                                                ctypes.c_int]
        L.tpurpc_server_stop.argtypes = [ctypes.c_void_p]
        L.tpurpc_call_copy_out.restype = ctypes.c_long
        L.tpurpc_call_copy_out.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                           ctypes.c_void_p, ctypes.c_size_t,
                                           ctypes.POINTER(ctypes.c_uint32)]
        L.tpurpc_call_reply.restype = ctypes.c_int
        L.tpurpc_call_reply.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t]
        L.tpurpc_call_reply_put.restype = ctypes.c_int
        L.tpurpc_call_reply_put.argtypes = [ctypes.c_void_p, ctypes.c_uint32,
                                            ctypes.c_uint64]
        L.tpurpc_tensor_step_answered.restype = None
        L.tpurpc_tensor_step_answered.argtypes = []
        L.tpurpc_kv_chunk_landed.restype = None
        L.tpurpc_kv_chunk_landed.argtypes = [ctypes.c_size_t]
        L.tpurpc_kv_pool_state.restype = None
        L.tpurpc_kv_pool_state.argtypes = [ctypes.c_long] * 3
        L.tpurpc_flag_set.restype = ctypes.c_int
        L.tpurpc_flag_set.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        L.tpurpc_call_fail.restype = ctypes.c_int
        L.tpurpc_call_fail.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                       ctypes.c_char_p]
        L.tpurpc_channel_open.restype = ctypes.c_void_p
        L.tpurpc_channel_open.argtypes = [ctypes.c_char_p, ctypes.c_int,
                                          ctypes.c_int, ctypes.c_long]
        L.tpurpc_channel_call.restype = ctypes.c_int
        L.tpurpc_channel_call.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t), ctypes.c_long, ctypes.c_char_p,
            ctypes.c_size_t]
        L.tpurpc_channel_put.restype = ctypes.c_int
        L.tpurpc_channel_put.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint32,
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_long, ctypes.c_char_p, ctypes.c_size_t]
        L.tpurpc_channel_get.restype = ctypes.c_int
        L.tpurpc_channel_get.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint32,
            ctypes.c_void_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t), ctypes.c_long, ctypes.c_char_p,
            ctypes.c_size_t]
        L.tpurpc_channel_close.argtypes = [ctypes.c_void_p]
        if L.tpurpc_global_init() != 0:
            raise RuntimeError("tpurpc_global_init failed")
        _LIB = L
    return _LIB


def crc32c(data: bytes | np.ndarray, init: int = 0) -> int:
    buf = np.ascontiguousarray(data).view(np.uint8) if isinstance(
        data, np.ndarray) else np.frombuffer(data, dtype=np.uint8)
    return int(lib().tpurpc_crc32c(
        init, buf.ctypes.data_as(ctypes.c_void_p), buf.nbytes))


def copy_crc32c(dst: np.ndarray, src: np.ndarray, init: int = 0,
                tables: bool = False) -> int:
    """`src`'s bytes into `dst` (both contiguous, the same size, not
    overlapping) and their crc32c, in ONE pass: each line is folded into
    the crc and copied while it sits in L1 (cpp/tbase/crc32c.h
    crc32c_copy_extend). How a payload is staged into a ring slot; the crc
    is `frame_in_place`'s. `tables` runs the table path whatever the cpu
    (tests)."""
    if dst.nbytes != src.nbytes:
        raise ValueError(f"{src.nbytes} bytes into room for {dst.nbytes}")
    fn = (lib().tpurpc_crc32c_copy_tables if tables
          else lib().tpurpc_crc32c_copy)
    return int(fn(init, _address(dst), _address(src), src.nbytes))


def staging_counters() -> dict:
    """/vars rpc_stage_fused_bytes: bytes staged with their crc32c in one
    pass (`copy_crc32c`, `ParkedCall.copy_into`)."""
    return {"rpc_stage_fused_bytes": int(lib().tpurpc_stage_fused_bytes())}


def stage_dump() -> dict:
    """This process's stage-clock table (cpp/tvar/stage_recorder.h): the
    object `/status?format=json` carries under "stages", cumulative since
    the library was loaded, for a process that serves no portal."""
    import json

    cap = 1 << 16
    while True:
        buf = ctypes.create_string_buffer(cap)
        n = lib().tpurpc_stage_dump(buf, cap)
        if n < cap:
            return json.loads(buf.value)
        cap = n + 1


class PoolBuffer:
    """A staging buffer carved from the registered ICI block pool,
    exposed to numpy/JAX zero-copy via the buffer protocol."""

    def __init__(self, nbytes: int):
        self._ptr = lib().tpurpc_block_alloc(nbytes)
        if not self._ptr:
            raise MemoryError(f"pool alloc of {nbytes} bytes failed")
        self.nbytes = nbytes
        self.registered = bool(
            lib().tpurpc_block_is_registered(self._ptr))
        self.array = np.ctypeslib.as_array(
            ctypes.cast(self._ptr, ctypes.POINTER(ctypes.c_uint8)),
            shape=(nbytes,),
        )

    def free(self):
        if self._ptr:
            lib().tpurpc_block_free(self._ptr)
            self._ptr = None
            self.array = None


def pool_id() -> int:
    """Descriptor identity of this process's shared pool (0 = none)."""
    return int(lib().tpurpc_pool_id())


def pool_epoch() -> int:
    """Current generation of this process's pool mapping (epoch fence)."""
    return int(lib().tpurpc_pool_epoch())


def lease_counters() -> tuple[int, int]:
    """(live pinned blocks, reaped pins) — the leak evidence: a healthy
    round ends pinned == 0 (the soaks read the same two on /pools)."""
    L = lib()
    return int(L.tpurpc_lease_pinned()), int(L.tpurpc_lease_reaped())


def transport_tiers() -> list[dict]:
    """The first-class Transport registry (ISSUE 12): one dict per
    registered endpoint type with its capability bits and op count —
    the uniform tcp/ici/shm_xproc/device tier story, introspected
    straight from the C++ seam."""
    L = lib()
    tiers = []
    name = ctypes.create_string_buffer(64)
    for t in range(int(L.tpurpc_transport_tier_count())):
        if L.tpurpc_transport_tier_name(t, name, len(name)) < 0:
            continue
        tiers.append({
            "name": name.value.decode(),
            "descriptor_capable": bool(
                L.tpurpc_transport_tier_descriptor_capable(t)),
            "zero_copy": bool(L.tpurpc_transport_tier_zero_copy(t)),
            "cross_process": bool(
                L.tpurpc_transport_tier_cross_process(t)),
            "one_sided": bool(L.tpurpc_transport_tier_one_sided(t)),
            "sgl_max": int(L.tpurpc_transport_tier_sgl_max(t)),
            "ops": int(L.tpurpc_transport_tier_ops(t)),
        })
    return tiers


def verbs_counters() -> dict:
    """One-sided verb plane counters (ISSUE 18): posted/completed verbs,
    bytes moved, stale-epoch rejects, CQ parks, plus the live window and
    pending-post gauges (leak evidence: a clean shutdown ends with
    windows == 0 and pending == 0)."""
    L = lib()
    return {
        "posted": int(L.tpurpc_verbs_posted()),
        "completed": int(L.tpurpc_verbs_completed()),
        "bytes": int(L.tpurpc_verbs_bytes()),
        "stale_rejects": int(L.tpurpc_verbs_stale_rejects()),
        "cq_parks": int(L.tpurpc_verbs_cq_parks()),
        "windows": int(L.tpurpc_verbs_windows()),
        "pending": int(L.tpurpc_verbs_pending()),
    }


class RingAbortedError(RuntimeError):
    """The staging ring was poisoned (device stream error / shutdown):
    parked acquires unblock with this instead of wedging forever."""


def slab_counters() -> tuple[int, int]:
    """(live slab slots, recycled-allocation count) — the zero-copy /
    recycle evidence the device-ring tests assert on."""
    L = lib()
    return int(L.tpurpc_slab_allocated()), int(L.tpurpc_slab_recycled())


class DeviceStagingRing:
    """Depth-N ring of registered staging slots (C++ DeviceStagingRing):
    the pipelined device path stages chunk i+1 while chunk i computes
    and chunk i-1 drains. acquire() hands slots out in FIFO order and
    blocks while all are in flight; complete() releases them."""

    def __init__(self, depth: int, slot_bytes: int):
        self._ptr = lib().tpurpc_ring_create(depth, slot_bytes)
        if not self._ptr:
            raise MemoryError(
                f"ring create ({depth} x {slot_bytes}B) failed")
        self.depth = int(lib().tpurpc_ring_depth(self._ptr))
        self.slot_bytes = int(lib().tpurpc_ring_slot_bytes(self._ptr))
        self.registered = bool(lib().tpurpc_ring_registered(self._ptr))
        self.slots = []
        for i in range(self.depth):
            p = lib().tpurpc_ring_slot(self._ptr, i)
            self.slots.append(np.ctypeslib.as_array(
                ctypes.cast(p, ctypes.POINTER(ctypes.c_uint8)),
                shape=(self.slot_bytes,)))

    def acquire(self, timeout_us: int = -1) -> int:
        # A free slot is handed out without a wait and without giving up
        # the interpreter lock; only a full ring is waited for, released.
        slot = int(_short_calls().tpurpc_ring_acquire(self._ptr, 0))
        if slot == -1 and timeout_us != 0:
            slot = int(lib().tpurpc_ring_acquire(self._ptr, timeout_us))
        if slot == -2:
            raise RingAbortedError("ring aborted (poisoned)")
        if slot < 0:
            raise TimeoutError("ring acquire timed out")
        return slot

    def complete(self, slot: int) -> None:
        if _short_calls().tpurpc_ring_complete(self._ptr, slot) != 0:
            raise ValueError(f"slot {slot} not in flight")

    def abort(self) -> None:
        """Poison the ring: every parked and future acquire raises
        RingAbortedError immediately (device-error escape hatch)."""
        lib().tpurpc_ring_abort(self._ptr)

    @property
    def aborted(self) -> bool:
        return bool(lib().tpurpc_ring_aborted(self._ptr))

    @property
    def inflight_highwater(self) -> int:
        return int(lib().tpurpc_ring_inflight_highwater(self._ptr))

    def close(self) -> None:
        if self._ptr:
            lib().tpurpc_ring_destroy(self._ptr)
            self._ptr = None
            self.slots = []


# Error codes of cpp/tbase/errno.h that the Python side answers with.
TERR_NO_METHOD = 4004
TERR_REQUEST = 4005
TERR_CLOSE = 4009
TERR_INTERNAL = 4010
# What kvpb.Cache/Get fails with for a (session, layer) that is not in the
# pool: never put, or evicted.
KV_NOT_FOUND = errno.ENOENT


def set_flag(name: str, value) -> None:
    """One of the framework's flags (what the portal's /flags lists), for
    the embedding process to choose before it serves or calls."""
    if lib().tpurpc_flag_set(name.encode(), str(value).encode()) != 0:
        raise ValueError(f"flag {name!r} is unknown or refuses {value!r}")


def tensor_step_answered() -> None:
    """/vars rpc_tensor_calls += 1: the D2H of one step's result is back
    (brpc_tpu/tensor_service.py counts there, and nowhere else)."""
    lib().tpurpc_tensor_step_answered()


def kv_chunk_landed(nbytes: int) -> None:
    """/vars rpc_kv_chunks += 1, rpc_kv_bytes_landed += nbytes: the word of
    one chunk of a Put is back from the device (brpc_tpu/kv_service.py
    counts there, on the lane's completion thread, and nowhere else)."""
    _short_calls().tpurpc_kv_chunk_landed(nbytes)


def kv_pool_state(pool_bytes: int, resident_bytes: int,
                  evicted: int = 0) -> None:
    """/vars rpc_kv_pool_bytes and rpc_kv_resident_bytes as they are now;
    rpc_kv_evictions += evicted."""
    _short_calls().tpurpc_kv_pool_state(pool_bytes, resident_bytes, evicted)


class ServerClosedError(RuntimeError):
    """The pull server's queue was closed: `take` has nothing more."""


class RpcError(RuntimeError):
    """A client call failed; `.code` is the call's error code."""

    def __init__(self, code: int, text: str):
        super().__init__(f"rpc failed ({code}): {text}")
        self.code = code


def _address(array: np.ndarray) -> ctypes.c_void_p:
    return ctypes.c_void_p(array.ctypes.data)


STEP, PUT, GET = 0, 1, 2  # ParkedCall.method (c_api.h TPURPC_METHOD_*)


class ParkedCall:
    """One call of the pull server's services between the C++ handler that
    parked it and its answer: `method` says which (STEP, PUT, GET), `nbytes`
    is its request attachment's length, `session` and `layer` are a Put's
    or a Get's. Exactly one of `reply` / `reply_put` / `fail` ends it."""

    __slots__ = ("_ptr", "nbytes", "method", "session", "layer")

    def __init__(self, ptr: int, nbytes: int, method: int = STEP,
                 session: int = 0, layer: int = 0):
        self._ptr = ptr
        self.nbytes = nbytes
        self.method = method
        self.session = session
        self.layer = layer

    def copy_into(self, view: np.ndarray, offset: int = 0) -> int:
        """The request attachment from byte `offset` on into `view` (uint8)
        and zeros into what is left of it where the attachment ends first,
        each block folded into the crc32c by the pass that copies it.
        Returns the crc32c of all of `view`, which is `frame_in_place`'s."""
        crc = ctypes.c_uint32()
        got = lib().tpurpc_call_copy_out(self._ptr, offset, _address(view),
                                         view.nbytes, ctypes.byref(crc))
        want = max(0, min(view.nbytes, self.nbytes - offset))
        if got != want:
            raise ValueError(f"copied {got} of {want} bytes")
        return int(crc.value)

    def _answering(self) -> int:
        ptr, self._ptr = self._ptr, None
        if not ptr:
            raise ValueError("the call has been answered already")
        return ptr

    def reply(self, body: np.ndarray,
              tail: np.ndarray | None = None) -> None:
        ptr = self._answering()
        body = body.reshape(-1).view(np.uint8)
        lib().tpurpc_call_reply(
            ptr, _address(body), body.nbytes,
            None if tail is None else _address(tail),
            0 if tail is None else tail.nbytes)

    def reply_put(self, word: int, admitted: int) -> None:
        """A Put's answer: the response's two integers, no attachment."""
        if self.method != PUT:
            raise ValueError("reply_put answers a Put")
        lib().tpurpc_call_reply_put(self._answering(), word, admitted)

    def fail(self, code: int, text: str) -> None:
        ptr, self._ptr = self._ptr, None
        if ptr:
            lib().tpurpc_call_fail(ptr, code, text.encode())

    def __del__(self):
        # A call dropped unanswered would hold the server's Join forever.
        self.fail(TERR_INTERNAL, "the call was dropped unanswered")


class PullServer:
    """The C API's pull server (cpp/trpc/c_api.h): tensorpb.Tensor/Step and
    kvpb.Cache/Put, /Get on a Server inside this process, listening on
    127.0.0.1 (TCP, the shm link after its handshake, and the builtin
    portal). `take` gives the parked calls of every method in the order
    they arrived; it blocks in C++ with the interpreter lock released."""

    def __init__(self, port: int = 0):
        self._ptr = lib().tpurpc_server_start(port)
        if not self._ptr:
            raise RuntimeError(f"tpurpc_server_start({port}) failed")
        self.port = int(lib().tpurpc_server_port(self._ptr))

    def take(self, timeout_us: int = -1) -> ParkedCall | None:
        """The next parked call; None on timeout; ServerClosedError once
        the queue is closed."""
        nbytes, status = ctypes.c_size_t(), ctypes.c_int()
        what = (ctypes.c_uint64 * 3)()
        ptr = lib().tpurpc_server_take(self._ptr, timeout_us,
                                       ctypes.byref(nbytes),
                                       ctypes.byref(status), what)
        if ptr:
            return ParkedCall(ptr, int(nbytes.value), *what)
        if status.value == -2:
            raise ServerClosedError("the pull server's queue is closed")
        return None

    def close_queue(self, code: int = TERR_CLOSE) -> None:
        """Fail what is parked and what arrives from now on with `code`;
        unblock every `take`."""
        if self._ptr:
            lib().tpurpc_server_close_queue(self._ptr, code)

    def stop(self) -> None:
        """close_queue, Stop, Join (waits until every taken call has been
        answered), free."""
        ptr, self._ptr = self._ptr, None
        if ptr:
            lib().tpurpc_server_stop(ptr)


def _reply(rc: int, err, out: np.ndarray | None = None, got: int = 0):
    """A client call's outcome: RpcError where it failed, else the `got`
    bytes of the reply attachment that `out` was given room for."""
    if rc != 0:
        raise RpcError(rc, err.value.decode("utf-8", "replace"))
    if out is not None:
        if got > out.nbytes:
            raise ValueError(f"reply of {got} bytes, room for {out.nbytes}")
        return out[:got]


class StepChannel:
    """One blocking client of the pull server's services (tests, the
    rehearsal, chip_smoke.py), no retry: `call` is tensorpb.Tensor/Step
    (attachment in, attachment out), `put` and `get` are kvpb.Cache's."""

    def __init__(self, port: int, ici: bool = False,
                 timeout_ms: int = 10000, host: str = "127.0.0.1"):
        self.timeout_ms = timeout_ms
        self._ptr = lib().tpurpc_channel_open(host.encode(), port, int(ici),
                                              timeout_ms)
        if not self._ptr:
            raise RuntimeError(f"no channel to {host}:{port} (ici={ici})")

    def call(self, request: np.ndarray, reply_cap: int | None = None,
             timeout_ms: int | None = None) -> np.ndarray:
        request = np.ascontiguousarray(request).reshape(-1).view(np.uint8)
        cap = request.nbytes + 64 if reply_cap is None else reply_cap
        out = np.empty(cap, dtype=np.uint8)
        got = ctypes.c_size_t()
        err = ctypes.create_string_buffer(256)
        rc = lib().tpurpc_channel_call(
            self._ptr, _address(request), request.nbytes, _address(out),
            cap, ctypes.byref(got),
            self.timeout_ms if timeout_ms is None else timeout_ms, err,
            len(err))
        return _reply(rc, err, out, got.value)

    def put(self, session: int, layer: int,
            data: np.ndarray) -> tuple[int, int]:
        """Put one layer of a session's cache; (word, admitted)."""
        data = np.ascontiguousarray(data).reshape(-1).view(np.uint8)
        word, admitted = ctypes.c_uint32(), ctypes.c_uint64()
        err = ctypes.create_string_buffer(256)
        rc = lib().tpurpc_channel_put(
            self._ptr, session, layer, _address(data), data.nbytes,
            ctypes.byref(word), ctypes.byref(admitted), self.timeout_ms,
            err, len(err))
        _reply(rc, err)
        return int(word.value), int(admitted.value)

    def get(self, session: int, layer: int, cap: int) -> np.ndarray:
        """The layer's bytes (at most `cap`); RpcError with `.code`
        KV_NOT_FOUND where the pool has none."""
        out = np.empty(cap, dtype=np.uint8)
        got = ctypes.c_size_t()
        err = ctypes.create_string_buffer(256)
        rc = lib().tpurpc_channel_get(
            self._ptr, session, layer, _address(out), cap, ctypes.byref(got),
            self.timeout_ms, err, len(err))
        return _reply(rc, err, out, got.value)

    def close(self) -> None:
        ptr, self._ptr = self._ptr, None
        if ptr:
            lib().tpurpc_channel_close(ptr)


def frame(correlation_id: int, payload: np.ndarray,
          out: np.ndarray | None = None) -> np.ndarray:
    """tpu_std-frame `payload` (any contiguous array) via the C++
    framework, which copies it behind the header + meta it writes and
    walks it for the crc32c; returns a uint8 view of the frame (in `out`
    if given). A payload staged in a ring slot is `frame_in_place`'s."""
    pay = np.ascontiguousarray(payload).view(np.uint8).reshape(-1)
    cap = pay.nbytes + 1024
    if out is None:
        out = np.empty(cap, dtype=np.uint8)
    elif out.nbytes < cap:
        raise ValueError("out buffer too small")
    n = lib().tpurpc_frame(
        correlation_id, pay.ctypes.data_as(ctypes.c_void_p), pay.nbytes,
        out.ctypes.data_as(ctypes.c_void_p), out.nbytes)
    if n < 0:
        raise ValueError("tpurpc_frame failed")
    return out[:n]


# Staging offset leaving room for header+meta of any in-place frame
# (12-byte header + ~30B meta pb, rounded way up).
IN_PLACE_HEADROOM = 64


def frame_in_place(correlation_id: int, buf: np.ndarray, payload_off: int,
                   payload_len: int, crc: int) -> tuple[int, int]:
    """Frame a payload that already resides at buf[payload_off:...]:
    writes header+meta right-justified before it -- no payload memcpy and
    no pass over the payload either: `crc` is its crc32c from the pass
    that staged it (`copy_crc32c`, `ParkedCall.copy_into`) and is what the
    meta embeds; one that is no integer is a TypeError. Returns
    (frame_off, frame_len)."""
    b = buf.view(np.uint8).reshape(-1)
    frame_off = ctypes.c_size_t()
    n = _short_calls().tpurpc_frame_in_place(
        correlation_id, b.ctypes.data_as(ctypes.c_void_p), payload_off,
        payload_len, ctypes.c_uint32(crc), ctypes.byref(frame_off))
    if n < 0:
        raise ValueError("tpurpc_frame_in_place failed (headroom < meta)")
    return int(frame_off.value), int(n)


def unframe(buf: np.ndarray) -> tuple[int, np.ndarray, int]:
    """Parse + checksum-verify ONE frame via the C++ framework.
    Returns (correlation_id, payload bytes (a view into buf), consumed)."""
    b = np.ascontiguousarray(buf).view(np.uint8).reshape(-1)
    cid = ctypes.c_uint64()
    off = ctypes.c_size_t()
    length = ctypes.c_size_t()
    n = lib().tpurpc_unframe(
        b.ctypes.data_as(ctypes.c_void_p), b.nbytes,
        ctypes.byref(cid), ctypes.byref(off), ctypes.byref(length))
    if n == -1:
        raise ValueError("incomplete frame")
    if n < 0:
        raise ValueError("corrupt frame (bad magic/meta/crc32c)")
    return int(cid.value), b[off.value:off.value + length.value], int(n)
