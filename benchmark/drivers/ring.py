"""Driver `ring`: the program's pipelined staging-ring path, host -> HBM ->
host, pass after pass over the payload until the window closes.

The timed path is the program's own entry, `_ChunkPipeline.run(1)` (stage
into the registered ring slot, C++ in-place framing, H2D, the jitted
integrity pass, D2H, crc32c against the framer's), built as
`device_path.run` builds it and called pass after pass: a repair of that
loop shows here. Each pass drains its last `depth` chunks before the next
begins (the entry has no time bound of its own). The benchmark adds the
clock, and a wrapper around the integrity pass that keeps each chunk's
newest answer to compare after the window.
"""
import time
from collections import deque

import numpy as np

from benchmark import device, payload, reference, stats, tracing

RAW_CONTROL_S = 2.0


def make_chunks(seed: int, payload_bytes: int, chunk_kb: int):
    """The payload from the seed, cut as `device_path.run` cuts it."""
    chunk_bytes = (chunk_kb << 10) & ~4095
    n_chunks = max(1, payload_bytes // chunk_bytes)
    words = payload.words(seed, 0, n_chunks * chunk_bytes // 4)
    per = chunk_bytes // 4
    return [words[i * per:(i + 1) * per] for i in range(n_chunks)]


def raw_link_gbps(chunks, dev, depth: int, seconds: float) -> float:
    """The plain control beside the ring: the same chunks through
    `jax.device_put` -> ready -> `np.asarray`, `depth` in flight, no ring,
    no framing, no kernel. Bytes counted once, as the ring's are."""
    import jax

    inflight, moved = deque(), 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        for c in chunks:
            inflight.append(jax.device_put(c, dev))
            while len(inflight) >= depth:
                x = inflight.popleft()
                x.block_until_ready()
                moved += np.asarray(x).nbytes
    while inflight:
        moved += np.asarray(inflight.popleft()).nbytes
    return stats.gbps(moved, time.monotonic() - t0)


def altered(touch):
    """Control `alter_word`: the integrity pass hands back the chunk with
    one word changed (an answer altered where it is produced)."""
    import jax.numpy as jnp

    def bad(x):
        y, chk = touch(x)
        return y.at[7].add(jnp.uint32(1)), chk
    return bad


class KeepingAnswers:
    """The integrity pass as the pipeline calls it, with each chunk's
    newest answer kept (the pipeline launches chunk k of every pass as
    call k mod n). The answers stay on the device until they are read
    after the window, so the device holds one payload more."""

    def __init__(self, touch, n_chunks: int):
        self.touch = touch
        self.last = [None] * n_chunks
        self.calls = 0

    def __call__(self, x):
        y, chk = self.touch(x)
        self.last[self.calls % len(self.last)] = y
        self.calls += 1
        return y, chk


def run(run) -> dict:
    from brpc_tpu import compile_cache, device_path, native

    tr = run.traffic
    depth = int(tr["depth"])
    native.build()
    run.mark("built")
    compile_cache.enable()
    dev = run.devices[0]
    chunks = make_chunks(run.seed, int(tr["payload_bytes"]),
                         int(tr["chunk_kb"]))
    n, chunk_bytes = len(chunks), chunks[0].nbytes
    touch = device_path._touch_kernel(chunk_bytes // 4, dev.platform)
    if run.control == "alter_word":
        touch = altered(touch)
    elif run.control is not None:
        raise ValueError(f"ring: unknown control {run.control!r}")
    touch = KeepingAnswers(touch, n)
    ring = native.DeviceStagingRing(depth, chunk_bytes + 1024)
    pipe = device_path._ChunkPipeline(ring, chunks, dev, touch, depth, False)
    run.mark("payload_and_ring_made")
    try:
        pipe.run(1)  # warm-up: compiles, first transfers (set-up)
        run.mark("warmed")
        pipe.dev_checks.clear()
        pipe.ok = True
        touch.calls = 0

        window = tracing.window_seconds(run.seconds, run.trace)
        with tracing.TraceWindow(run.trace) as tw:
            t0 = time.monotonic()
            t_end = t0 + window
            while time.monotonic() < t_end:
                with tw.span("ring.pass(stage+frame+h2d+kernel+d2h+crc32c)"):
                    pipe.run(1)  # the pass in flight at the close is finished
            window_s = time.monotonic() - t0
        launched = touch.calls
        peak = device.memory_peak_bytes(run.devices)
        highwater = ring.inflight_highwater
        raw = (raw_link_gbps(chunks, dev, depth, RAW_CONTROL_S)
               if run.trace else None)
    finally:
        ring.close()

    returned = {k: np.asarray(y) for k, y in enumerate(touch.last)
                if y is not None}
    got = reference.check_ring(chunks, returned, pipe.dev_checks, launched)
    got["program_crc32c_failed"] = 0 if pipe.ok else 1
    failed = max(got["chunks_word_wrong"], got["chunks_bytes_wrong"],
                 got["program_crc32c_failed"]) + got["chunks_unanswered"]
    good = launched - failed
    summary = tw.summary()
    run.notes.update(chunks=n, chunk_bytes=chunk_bytes, passes=launched / n,
                     ring_inflight_highwater=int(highwater))
    if summary:
        run.notes["trace_modules"] = sorted(
            {m for c in summary["chips"].values() for m in c["modules"]})[:8]
    return {
        "attempted": launched, "failed": failed, "window_s": window_s,
        "t_first_op": t0, "memory_peak_bytes": peak,
        "end_to_end": {"goodput_gbps": stats.gbps(good * chunk_bytes,
                                                  window_s)},
        "checks": [(k, v, 0) for k, v in got.items()],
        "trace": summary, "chunk_bytes": chunk_bytes,
        "raw_link_gbps": raw,
        "device_kind": dev.device_kind,
    }
