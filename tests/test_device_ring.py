"""Tier-1: the pipelined DMA staging ring + one-sided descriptor path
(ISSUE 9).

Runs on the virtual CPU mesh (conftest pins JAX_PLATFORMS=cpu): the cpu
backend is explicitly tolerated — the ring must still move framed chunks
through the full C++ staging path with every integrity check live, and
the run must never be silently skipped (the record keys are asserted, a
missing device path is a failure, not a skip).
"""
import json
import subprocess
import threading
import time

import numpy as np
import pytest


@pytest.fixture(scope="module")
def native(cpp_build):
    from brpc_tpu import native as n
    n.lib()  # loads build/libtpurpc.so produced by the cpp_build fixture
    return n


RECORD_KEYS = {
    "device_path_gbps", "device_path_ok", "device_path_ring_depth",
    "device_path_chunk_bytes", "device_path_inflight_highwater",
    "device_path_registered_staging", "device_path_device",
}


def test_ring_pipeline_correctness_and_speedup(cpp_build, native):
    """The smoke's ring pass on the cpu backend: per-chunk crc32c and every
    on-device word verified after the overlapped pipeline, FIFO window
    respected, and the record is the one path's alone (the per-copy loop's
    keys went with it, ISSUE 31)."""
    from brpc_tpu.device_path import run

    out = run(payload_mb=4, reps=4, ring_depth=4, chunk_kb=508)
    # Never silently skipped: the run must report a real device record.
    # Exactly these: the serial baseline's figure and the overlap ratio
    # are absent, and nothing else has come in their place.
    assert set(out) == RECORD_KEYS
    assert out["device_path_ok"], "per-chunk crc32c verification failed"
    assert out["device_path_gbps"] > 0
    assert out["device_path_ring_depth"] == 4
    assert out["device_path_chunk_bytes"] == 508 << 10
    assert out["device_path_inflight_highwater"] <= 4
    assert out["device_path_device"].startswith("cpu:")
    assert out["device_path_registered_staging"], \
        "staging ring must come from registered pool memory"


def test_ring_fifo_and_recycling(native):
    ring = native.DeviceStagingRing(4, 64 << 10)
    assert ring.registered
    # FIFO order, window bounded by depth.
    slots = [ring.acquire() for _ in range(4)]
    assert slots == [0, 1, 2, 3]
    with pytest.raises(TimeoutError):
        ring.acquire(timeout_us=1000)  # window full
    # Out-of-order completes are held until predecessors finish.
    ring.complete(slots[1])
    with pytest.raises(TimeoutError):
        ring.acquire(timeout_us=1000)  # slot 0 still pins the window
    ring.complete(slots[0])
    assert ring.acquire() == 0  # both freed, FIFO resumes at 0
    assert ring.inflight_highwater == 4
    ring.close()

    # Ring slots recycle through the slab classes on close.
    live0, _ = native.slab_counters()
    r2 = native.DeviceStagingRing(2, 64 << 10)
    live_open, _ = native.slab_counters()
    assert live_open == live0 + 2
    r2.close()
    live_closed, recycled = native.slab_counters()
    assert live_closed == live0
    assert recycled >= 0


def test_a_full_rings_acquire_waits_with_the_interpreter_lock_released(
        native):
    """ISSUE 32: a free slot is handed out through `native._short_calls`,
    whose calls keep the interpreter lock; a full ring must not be waited
    for that way, or every thread of the process would stand still. While
    one thread waits for a slot another one runs Python, completes a slot,
    and the waiter gets it; an aborted ring reads aborted on either way."""
    ring = native.DeviceStagingRing(2, 64 << 10)
    assert [ring.acquire(), ring.acquire()] == [0, 1]
    with pytest.raises(TimeoutError):
        ring.acquire(timeout_us=0)  # full, asked not to wait: at once
    got = []
    waiter = threading.Thread(
        target=lambda: got.append(ring.acquire(timeout_us=20_000_000)))
    t0 = time.monotonic()
    waiter.start()
    spins = 0
    while time.monotonic() - t0 < 0.3:  # runs only if the lock is free
        spins += 1
    assert waiter.is_alive() and spins > 10_000, spins
    ring.complete(0)
    waiter.join(timeout=10)
    assert not waiter.is_alive() and got == [0]
    assert time.monotonic() - t0 < 5
    ring.abort()
    with pytest.raises(native.RingAbortedError):
        ring.acquire(timeout_us=0)
    with pytest.raises(native.RingAbortedError):
        ring.acquire(timeout_us=1000)
    ring.close()
    # The two handles are one library: same functions, same declarations.
    for name in ("tpurpc_ring_acquire", "tpurpc_ring_complete",
                 "tpurpc_frame_in_place"):
        short = getattr(native._short_calls(), name)
        long_ = getattr(native.lib(), name)
        assert (short.restype, short.argtypes) == (long_.restype,
                                                   long_.argtypes)


def test_frame_in_place_skips_payload_copy(native):
    """ISSUE 9 satellite: a payload staged inside the destination pool
    buffer (`copy_crc32c`) is framed by a header + meta written before it
    from that crc — the parsed payload view IS the staged region (no
    memcpy), and the crc in the meta is the staged bytes'."""
    buf = native.PoolBuffer(1 << 20)
    payload = np.arange(4096, dtype=np.uint32)
    off = native.IN_PLACE_HEADROOM
    region = buf.array[off:off + payload.nbytes]
    crc = native.copy_crc32c(region, payload.view(np.uint8))
    assert crc == native.crc32c(payload)
    frame_off, n = native.frame_in_place(42, buf.array, off, payload.nbytes,
                                         crc)
    assert frame_off + n == off + payload.nbytes
    fr = buf.array[frame_off:frame_off + n]
    cid, pay, _ = native.unframe(fr)
    assert cid == 42
    # Zero-copy proof: the parsed payload view IS the staged region.
    assert pay.ctypes.data == region.ctypes.data
    assert np.array_equal(pay.view(np.uint32), payload)
    # A mutation through the original region is visible in the frame.
    region.view(np.uint32)[0] ^= 0xFFFFFFFF
    with pytest.raises(ValueError):
        native.unframe(fr)  # crc now mismatches: same bytes, one copy
    region.view(np.uint32)[0] ^= 0xFFFFFFFF
    buf.free()


def test_descriptor_attachment_roundtrips_through_real_server(cpp_build):
    """One-sided pool descriptor through a REAL server (echo_bench
    --pool-desc --ici): the attachment crosses the seam as a (pool_id,
    offset, len, crc32c) reference, the server answers with the crc it
    computed from the in-place view, and zero inline payload bytes ride
    the frame."""
    exe = cpp_build / "echo_bench"
    proc = subprocess.run(
        [str(exe), "--json", "--ici", "--pool-desc"],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.strip().startswith("{"))
    out = json.loads(line)
    assert out["pool_desc_zero_copy"] == 1
    assert out["pool_desc_calls"] > 0
    assert out["pool_desc_mbps"] > 0
