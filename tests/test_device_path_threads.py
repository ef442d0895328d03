"""Who runs on which thread in the staging-ring pass (ISSUE 26, 32): the
caller stages, a dispatch thread puts each chunk on the device and a
completion thread retires it, both living inside `run()` and both in launch
order, depth 1 stays on one thread, an error on any of the three leaves
`run()` as itself with the ring aborted and no thread behind. CPU backend;
every test has a time limit of its own, so a hang fails here and stalls
nothing."""
import signal
import sys
import threading

import numpy as np
import pytest

from brpc_tpu import spans

CHUNK_BYTES, N_CHUNKS, RING_DEPTH = 64 << 10, 6, 3
LIMIT_S = 60


@pytest.fixture(autouse=True)
def time_limit():
    def expired(signum, frame):
        raise TimeoutError(f"the test ran over {LIMIT_S} s: a thread hangs")

    before = signal.signal(signal.SIGALRM, expired)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


@pytest.fixture
def make_pipeline(cpp_build, monkeypatch):
    """make_pipeline(depth, touch=None, ring_depth=3) ->
    (pipeline, ring, chunks) on the CPU backend, compiled and warm."""
    import jax

    from brpc_tpu import device_path, native

    # A wedge ends inside the test's limit.
    monkeypatch.setattr(device_path, "ACQUIRE_TIMEOUT_US", 10_000_000)
    dev = jax.devices("cpu")[0]
    per = CHUNK_BYTES // 4
    words = np.arange(N_CHUNKS * per, dtype=np.uint32) * np.uint32(2654435761)
    chunks = [words[i * per:(i + 1) * per] for i in range(N_CHUNKS)]
    kernel = device_path._touch_kernel(per, dev.platform)
    rings = []

    def make(depth, touch=None, ring_depth=RING_DEPTH):
        ring = native.DeviceStagingRing(ring_depth, CHUNK_BYTES + 1024)
        rings.append(ring)
        warm = device_path._ChunkPipeline(ring, chunks, dev, kernel, depth)
        warm.run(1)  # compile, first transfers
        pipe = device_path._ChunkPipeline(
            ring, chunks, dev, kernel if touch is None else touch(kernel),
            depth)
        spans.clear()
        return pipe, ring, chunks

    yield make
    for ring in rings:
        ring.close()


def thread_ids(name):
    return {rec[4] for rec in spans.snapshot() if rec[0] == name}


def host_words(chunks, passes):
    from brpc_tpu import device_path

    return [device_path._integrity_word_host(c) for c in chunks] * passes


def test_dispatches_and_retires_in_launch_order_on_two_other_threads(
        make_pipeline):
    pipe, ring, chunks = make_pipeline(3)
    calls = []

    def on_this_thread(x, touch=pipe.touch):
        calls.append(threading.get_ident())
        return touch(x)

    pipe.touch = on_this_thread
    threads = threading.active_count()
    pipe.run(3)
    assert threading.active_count() == threads
    assert pipe.ok
    assert pipe.dev_checks == host_words(chunks, 3)
    assert ring.inflight_highwater <= 3
    me = threading.get_ident()
    assert thread_ids("ring.launch") == {me}
    (dispatcher,) = thread_ids("ring.dispatch")
    (completions,) = thread_ids("ring.retire")
    assert len({me, dispatcher, completions}) == 3
    # touch: the dispatch thread's, every chunk
    assert calls == [dispatcher] * (3 * N_CHUNKS)
    for name in ("ring.launch", "ring.dispatch", "ring.retire"):
        in_order = [rec[3] for rec in spans.snapshot() if rec[0] == name]
        assert in_order == [(p, k) for p in (1, 2, 3)
                            for k in range(N_CHUNKS)], name


def test_depth_one_stays_on_the_calling_thread(make_pipeline):
    pipe, ring, chunks = make_pipeline(1)
    highwater = ring.inflight_highwater  # the warm-up's, at depth 1 too
    threads = threading.active_count()
    pipe.run(2)
    assert pipe.ok and pipe.dev_checks == host_words(chunks, 2)
    assert highwater == ring.inflight_highwater == 1
    names = {rec[0] for rec in spans.snapshot()}
    assert {"ring.dispatch", "ring.retire"} <= names
    assert "ring.drain" not in names
    assert {rec[4] for rec in spans.snapshot()} == {threading.get_ident()}
    assert threading.active_count() == threads


def test_the_per_copy_loop_is_refused(make_pipeline):
    """ISSUE 31: the sixth parameter stays for benchmark/drivers/ring.py,
    which passes False; anything truthy names the issue and stores nothing."""
    from brpc_tpu import device_path

    pipe, ring, chunks = make_pipeline(1)
    with pytest.raises(ValueError, match="ISSUE 31"):
        device_path._ChunkPipeline(ring, chunks, pipe.dev, pipe.touch, 1,
                                   True)
    again = device_path._ChunkPipeline(ring, chunks, pipe.dev, pipe.touch, 1,
                                       False)
    assert not hasattr(again, "copy_mode")


class CopyBackFailed(Exception):
    pass


class NeverComesBack:
    """What a device error looks like to the completion thread."""

    def __array__(self, *args, **kwargs):
        raise CopyBackFailed("the copy back failed")


def failing_copy_back(kernel):
    calls = []

    def touch(x):
        y, chk = kernel(x)
        calls.append(1)
        return (NeverComesBack() if len(calls) == 2 else y), chk
    return touch


class DispatchFailed(Exception):
    pass


class StageFailed(Exception):
    pass


def failing_dispatch(kernel):
    calls = []

    def touch(x):
        calls.append(1)
        if len(calls) == 3:
            raise DispatchFailed("the dispatch failed")
        return kernel(x)
    return touch


@pytest.mark.parametrize("depth", [1, 3])
@pytest.mark.parametrize("touch, error", [
    (failing_copy_back, CopyBackFailed),  # on the completion thread
    (failing_dispatch, DispatchFailed),   # on the dispatch thread
    (None, StageFailed),                  # on the caller, in its own pass
])
def test_an_error_on_any_thread_is_what_run_raises(make_pipeline, monkeypatch,
                                                   touch, error, depth):
    """At depth 1 the one thread that does all three meets it itself."""
    from brpc_tpu import native

    pipe, ring, chunks = make_pipeline(depth, touch=touch)
    if touch is None:
        real, calls = native.copy_crc32c, []

        def failing_stage(view, chunk):
            calls.append(1)
            if len(calls) == 4:
                raise StageFailed("the pass over the bytes failed")
            return real(view, chunk)

        monkeypatch.setattr(native, "copy_crc32c", failing_stage)
    threads = threading.active_count()
    with pytest.raises(error):
        pipe.run(2)
    assert ring.aborted
    assert threading.active_count() == threads
    # What was retired before the error is in launch order; nobody can
    # launch on the poisoned ring again.
    assert pipe.dev_checks == host_words(chunks, 1)[:len(pipe.dev_checks)]
    with pytest.raises(native.RingAbortedError):
        pipe.run(1)
    assert threading.active_count() == threads


def test_two_runs_in_a_row_leave_no_thread_and_retire_everything(
        make_pipeline):
    pipe, ring, chunks = make_pipeline(3)
    threads = threading.active_count()
    pipe.run(1)
    assert threading.active_count() == threads
    pipe.run(1)
    assert threading.active_count() == threads
    assert pipe.ok and pipe.dev_checks == host_words(chunks, 2)
    for name in ("ring.launch", "ring.retire"):
        assert sum(rec[0] == name for rec in spans.snapshot()) == 2 * N_CHUNKS
    for name in ("ring.dispatch", "ring.retire"):
        assert len(thread_ids(name)) <= 2, name  # one a call, ids may recur
    assert ring.inflight_highwater <= 3


def test_the_pipelines_depth_bounds_the_chunks_in_flight(make_pipeline):
    """Depth 2 over a ring of 4 slots, with the interpreter switching
    threads as often as it can: the credit, not the ring, is what holds
    the caller back, and both hand-overs keep the order."""
    pipe, ring, chunks = make_pipeline(2, ring_depth=4)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pipe.run(20)
    finally:
        sys.setswitchinterval(interval)
    assert pipe.ok and pipe.dev_checks == host_words(chunks, 20)
    assert ring.inflight_highwater == 2
