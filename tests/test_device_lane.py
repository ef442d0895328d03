"""`device_path.DeviceLane` (ISSUE 29): the long-lived lane a served handler
submits to, and `_ChunkPipeline.run` as a loop over `submit` on one -- the
ring cell and the served call run one piece of code. Its two helper
threads (ISSUE 32: a dispatch thread between the submitter and the
completion thread): order, errors on either, the drain. CPU backend; the
spans, words and crc verdicts are held to what `_ChunkPipeline` gave
before the lane existed (the assertions of test_device_path_spans /
test_device_path_threads, reused)."""
import signal
import sys
import threading
import time

import numpy as np
import pytest
from test_device_path_spans import (DISPATCH_CHILDREN, LAUNCH_CHILDREN,
                                    RETIRE_CHILDREN)

from brpc_tpu import native, spans, tensor_reference
from brpc_tpu.native import IN_PLACE_HEADROOM as HEADROOM

CHUNK_BYTES, N_CHUNKS, DEPTH = 64 << 10, 6, 3
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
def parts(cpp_build):
    """(device_path, ring, dev, kernel, chunks), compiled and warm."""
    import jax

    from brpc_tpu import device_path

    dev = jax.devices("cpu")[0]
    per = CHUNK_BYTES // 4
    words = np.arange(N_CHUNKS * per, dtype=np.uint32) * np.uint32(2654435761)
    chunks = [words[i * per:(i + 1) * per] for i in range(N_CHUNKS)]
    kernel = device_path._touch_kernel(per, dev.platform)
    ring = native.DeviceStagingRing(DEPTH, CHUNK_BYTES + 1024)
    device_path._ChunkPipeline(ring, chunks, dev, kernel, DEPTH,
                               False).run(1)  # compile, first transfers
    spans.clear()
    yield device_path, ring, dev, kernel, chunks
    ring.close()


def host_words(device_path, chunks, passes):
    return [device_path._integrity_word_host(c) for c in chunks] * passes


def filler(chunk):
    """What `submit` asks of a fill: the bytes into the slot, their crc32c
    back, from one pass."""
    return lambda view: native.copy_crc32c(view, chunk)


def test_a_pass_is_a_loop_over_submit_on_a_lane(parts, monkeypatch):
    device_path, ring, dev, kernel, chunks = parts
    lanes, submits = [], []
    real_init = device_path.DeviceLane.__init__
    real_submit = device_path.DeviceLane.submit

    def init(self, *a, **kw):
        lanes.append(self)
        real_init(self, *a, **kw)

    def submit(self, fill, nbytes, token, correlation_id=1):
        submits.append((token, nbytes, correlation_id))
        real_submit(self, fill, nbytes, token, correlation_id)

    monkeypatch.setattr(device_path.DeviceLane, "__init__", init)
    monkeypatch.setattr(device_path.DeviceLane, "submit", submit)
    pipe = device_path._ChunkPipeline(ring, chunks, dev, kernel, DEPTH, False)
    threads = threading.active_count()
    pipe.run(2)
    assert threading.active_count() == threads  # the lane lived inside run()
    assert len(lanes) == 1 and lanes[0].failure is None
    assert submits == [((p, k), CHUNK_BYTES, k + 1)
                       for p in (1, 2) for k in range(N_CHUNKS)]
    # What the pipeline gave before the lane: words in launch order, every
    # crc verdict good, the ring's window kept.
    assert pipe.ok and pipe.dev_checks == host_words(device_path, chunks, 2)
    assert ring.inflight_highwater <= DEPTH


def test_run_over_the_lane_gives_the_spans_it_gave_before(parts):
    device_path, ring, dev, kernel, chunks = parts
    pipe = device_path._ChunkPipeline(ring, chunks, dev, kernel, DEPTH, False)
    pipe.run(1)
    me = threading.get_ident()
    by_name = {}
    for name, start, end, request, thread in spans.snapshot():
        by_name.setdefault(name, []).append((request, thread, start, end))
    per_chunk = (LAUNCH_CHILDREN | DISPATCH_CHILDREN | RETIRE_CHILDREN
                 | {"ring.launch", "ring.dispatch", "ring.retire"})
    assert set(by_name) == per_chunk | {"ring.pass", "ring.drain"}
    for name in LAUNCH_CHILDREN | {"ring.launch", "ring.pass", "ring.drain"}:
        assert {t for _, t, _, _ in by_name[name]} == {me}, name
    (dispatcher,) = {t for _, t, _, _ in by_name["ring.dispatch"]}
    (completions,) = {t for _, t, _, _ in by_name["ring.retire"]}
    assert len({me, dispatcher, completions}) == 3
    for name in DISPATCH_CHILDREN:
        assert {t for _, t, _, _ in by_name[name]} == {dispatcher}, name
    for name in RETIRE_CHILDREN:
        assert {t for _, t, _, _ in by_name[name]} == {completions}, name
    for name in per_chunk:
        assert [r for r, *_ in by_name[name]] == [
            (1, k) for k in range(N_CHUNKS)], name
    # The drain is the close of the lane, under a ring.pass of its own.
    (_, _, d0, d1), = by_name["ring.drain"]
    assert any(s <= d0 and d1 <= e for _, _, s, e in by_name["ring.pass"])


def test_a_lane_survives_three_passes_worth_of_submits(parts):
    device_path, ring, dev, kernel, chunks = parts
    done = []
    threads = threading.active_count()
    lane = device_path.DeviceLane(
        ring, dev, kernel, DEPTH,
        lambda token, back, word, good: done.append(
            (token, word, good, bytes(back.view(np.uint8)[:16]))))
    assert threading.active_count() == threads + 2
    helpers = list(lane._helpers)
    assert [h.name for h in helpers] == ["ring.dispatch", "ring.completions"]
    for p in range(3):
        for k, chunk in enumerate(chunks):
            lane.submit(filler(chunk), CHUNK_BYTES, (p, k), k + 1)
        assert lane._helpers == helpers and all(h.is_alive() for h in helpers)
        assert threading.active_count() == threads + 2  # never re-started
    lane.close()
    assert threading.active_count() == threads and lane.failure is None
    assert [t for t, *_ in done] == [(p, k) for p in range(3)
                                     for k in range(N_CHUNKS)]
    assert [w for _, w, _, _ in done] == host_words(device_path, chunks, 3)
    assert all(good for _, _, good, _ in done)
    assert [head for *_, head in done] == [
        bytes(c.view(np.uint8)[:16]) for c in chunks] * 3
    for name in ("ring.dispatch", "ring.retire"):
        assert len({rec[4] for rec in spans.snapshot()
                    if rec[0] == name}) == 1, name
    assert ring.inflight_highwater <= DEPTH


def test_requests_of_other_sizes_share_one_lane(parts):
    """Independent requests: each submit brings its own size and token."""
    device_path, ring, dev, _, chunks = parts
    key = 0x0F0F1234
    kernel = device_path._tensor_step_kernel(key, dev.platform)
    got = {}
    lane = device_path.DeviceLane(
        ring, dev, kernel, DEPTH,
        lambda token, back, word, good: got.__setitem__(
            token, back.tobytes() + int(word).to_bytes(4, "little")),
        verify=False)
    sent = {}

    def filling(x, token):
        def fill(view):  # the caller's own span around its copy
            with spans.span("tensor.fill", token):
                return native.copy_crc32c(view, x)
        return fill

    for token, nbytes in enumerate([16, 4096, CHUNK_BYTES, 40, 4096]):
        x = np.random.default_rng(token).integers(0, 256, nbytes,
                                                  dtype=np.uint8)
        sent[token] = x
        lane.submit(filling(x, token), nbytes, token)
    lane.close()
    assert got == {t: tensor_reference.step(x, key) for t, x in sent.items()}
    names = {rec[0] for rec in spans.snapshot()}
    assert "tensor.fill" in names and "ring.stage" not in names
    assert "ring.verify" not in names  # not the identity: no crc to hold
    # The caller's span lies inside the lane's ring.launch of that request.
    by = {(rec[0], rec[3]): rec for rec in spans.snapshot()}
    for token in sent:
        _, f0, f1, _, thread = by["tensor.fill", token]
        _, l0, l1, _, launcher = by["ring.launch", token]
        assert l0 <= f0 and f1 <= l1 and thread == launcher


def test_depth_one_retires_inside_submit_on_the_callers_thread(parts):
    device_path, ring, dev, kernel, chunks = parts
    done = []
    threads = threading.active_count()
    lane = device_path.DeviceLane(
        ring, dev, kernel, 1, lambda token, *rest: done.append(token))
    assert threading.active_count() == threads
    for k, chunk in enumerate(chunks):
        lane.submit(filler(chunk), CHUNK_BYTES, k)
        assert done[-1] == k
    lane.close()
    assert lane._helpers == [] and threading.active_count() == threads
    assert {rec[4] for rec in spans.snapshot()} == {threading.get_ident()}
    # The same three parents a chunk, one after the other on this thread.
    for k in range(len(chunks)):
        times = [t for name in ("ring.launch", "ring.dispatch", "ring.retire")
                 for rec in spans.snapshot() if rec[0] == name and rec[3] == k
                 for t in rec[1:3]]
        assert len(times) == 6 and times == sorted(times)


@pytest.mark.parametrize("hold_the_retire", [False, True],
                         ids=["as_it_comes", "launcher_runs_ahead"])
def test_an_error_behind_the_launcher_abandons_what_follows(
        parts, monkeypatch, hold_the_retire):
    """Whichever thread is ahead: with the submitter as far ahead as the
    credits let it be, nothing is left of them when the retire fails, and
    the submits after it still meet the aborted ring at once (the timeout
    is set longer than the test's own limit)."""
    device_path, ring, dev, kernel, chunks = parts
    monkeypatch.setattr(device_path, "ACQUIRE_TIMEOUT_US",
                        2 * LIMIT_S * 1_000_000)
    go = threading.Event()
    if not hold_the_retire:
        go.set()

    class NeverComesBack:
        def __array__(self, *args, **kwargs):
            go.wait()
            raise OSError("the copy back failed")

    calls = []

    def failing(x):
        y, w = kernel(x)
        calls.append(1)
        if len(calls) == DEPTH + 1:  # the submitter has used every credit
            go.set()
        return (NeverComesBack() if len(calls) == 2 else y), w

    done, abandoned = [], []
    lane = device_path.DeviceLane(
        ring, dev, failing, DEPTH, lambda token, *rest: done.append(token),
        on_abandon=abandoned.append)
    launched = []
    with pytest.raises(native.RingAbortedError):
        for k, chunk in enumerate(chunks):
            lane.submit(filler(chunk), CHUNK_BYTES, k)
            launched.append(k)
    lane.close()
    if hold_the_retire:
        assert len(launched) == DEPTH + 1
    assert isinstance(lane.failure, OSError) and ring.aborted
    # Every chunk handed over was answered one way or the other, in order.
    assert done == [0] and abandoned == launched[1:]
    with pytest.raises(native.RingAbortedError):
        lane.submit(filler(chunks[0]), CHUNK_BYTES, 99)


@pytest.fixture
def deep(parts):
    """A ring of four slots beside the fixture's three: the depth both
    chip cells run at, one chunk in each thread's hands and one between."""
    ring = native.DeviceStagingRing(4, CHUNK_BYTES + 1024)
    yield ring
    ring.close()


def test_answers_come_in_submit_order_with_both_helpers_at_depth_four(
        parts, deep):
    """One submitter, one FIFO, one dispatcher, one FIFO, one retirer --
    with the interpreter switching threads as often as it can, so that a
    hand-over that lost or swapped a chunk would show."""
    device_path, _, dev, kernel, chunks = parts
    passes, dispatched, done = 12, [], []

    def counting(x):
        dispatched.append(threading.get_ident())
        return kernel(x)

    lane = device_path.DeviceLane(
        deep, dev, counting, 4,
        lambda token, back, word, good: done.append((token, word, good)))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for p in range(passes):
            for k, chunk in enumerate(chunks):
                lane.submit(filler(chunk), CHUNK_BYTES, (p, k), k + 1)
        lane.close()
    finally:
        sys.setswitchinterval(interval)
    assert lane.failure is None
    assert [t for t, _, _ in done] == [(p, k) for p in range(passes)
                                       for k in range(N_CHUNKS)]
    assert [w for _, w, _ in done] == host_words(device_path, chunks, passes)
    assert all(good for _, _, good in done)
    # The kernel ran on one thread, neither the submitter's nor the
    # retirer's, once a chunk.
    assert len(dispatched) == passes * N_CHUNKS
    (dispatcher,) = set(dispatched)
    retirers = {rec[4] for rec in spans.snapshot() if rec[0] == "ring.retire"}
    assert dispatcher not in retirers | {threading.get_ident()}
    assert deep.inflight_highwater <= 4


class PutFailed(Exception):
    pass


def failing_kernel(kernel, bad):
    """The jitted call raises on its `bad`-th chunk (from 0)."""
    calls = []

    def touch(x):
        calls.append(1)
        if len(calls) - 1 == bad:
            raise PutFailed("the dispatch failed")
        return kernel(x)
    return touch


def failing_h2d(monkeypatch, device_path, bad):
    """`device_put` raises on the `bad`-th chunk (from 0)."""
    real, calls = device_path._h2d, []

    def h2d(view, dev):
        calls.append(1)
        if len(calls) - 1 == bad:
            raise PutFailed("the H2D failed")
        return real(view, dev)

    monkeypatch.setattr(device_path, "_h2d", h2d)


@pytest.mark.parametrize("bad", [0, 2, 5])
def test_an_error_on_the_dispatch_thread_abandons_it_and_what_follows(
        parts, deep, monkeypatch, bad):
    """ISSUE 32: chunks ahead of the failed one are answered, it and every
    later one go through the completion thread's queue marked abandoned, in
    submit order and after the last answer; `failure` is that error; the
    next submit meets the aborted ring; `close()` leaves no thread and every
    credit back."""
    device_path, _, dev, kernel, chunks = parts
    monkeypatch.setattr(device_path, "ACQUIRE_TIMEOUT_US",
                        2 * LIMIT_S * 1_000_000)
    events = []
    threads = threading.active_count()
    lane = device_path.DeviceLane(
        deep, dev, failing_kernel(kernel, bad), 4,
        lambda token, back, word, good: events.append(("done", token, good)),
        on_abandon=lambda token: events.append(("abandoned", token)))
    launched = []
    try:
        for k, chunk in enumerate(chunks + chunks):
            lane.submit(filler(chunk), CHUNK_BYTES, k)
            launched.append(k)
    except native.RingAbortedError:
        pass
    else:
        pytest.fail("twelve submits at depth 4 outran an error on chunk "
                    f"{bad}")
    lane.close()
    assert threading.active_count() == threads and lane._helpers == []
    assert isinstance(lane.failure, PutFailed) and deep.aborted
    assert len(launched) > bad
    assert events == ([("done", k, True) for k in range(bad)]
                      + [("abandoned", k) for k in launched[bad:]])
    with pytest.raises(native.RingAbortedError):
        lane.submit(filler(chunks[0]), CHUNK_BYTES, 99)
    assert lane._credits.qsize() == 4  # every credit is back


@pytest.mark.parametrize("bad", [0, 2, 5])
def test_an_h2d_that_raises_leaves_submit_as_itself(parts, deep, monkeypatch,
                                                    bad):
    """The H2D is the submitter's (it copies the slot's bytes where the
    fill left them): its error is not a helper thread's. `submit` raises it,
    the ring is aborted, what was submitted before it is answered, nothing
    is abandoned and `failure` stays empty."""
    device_path, _, dev, kernel, chunks = parts
    failing_h2d(monkeypatch, device_path, bad)
    done, abandoned = [], []
    threads = threading.active_count()
    lane = device_path.DeviceLane(
        deep, dev, kernel, 4, lambda token, *rest: done.append(token),
        on_abandon=abandoned.append)
    with pytest.raises(PutFailed):
        for k, chunk in enumerate(chunks):
            lane.submit(filler(chunk), CHUNK_BYTES, k)
    assert deep.aborted
    with pytest.raises(native.RingAbortedError):
        lane.submit(filler(chunks[0]), CHUNK_BYTES, 99)
    lane.close()
    assert threading.active_count() == threads and lane._helpers == []
    assert done == list(range(bad)) and abandoned == []
    assert lane.failure is None


def test_the_first_error_is_the_one_kept_whichever_helper_met_it(
        parts, deep, monkeypatch):
    """The retire of chunk 1 fails while chunk 3 is in the dispatch
    thread's hands, which then fails too: `failure` stays the first."""
    device_path, _, dev, kernel, chunks = parts
    monkeypatch.setattr(device_path, "ACQUIRE_TIMEOUT_US",
                        2 * LIMIT_S * 1_000_000)
    retire_failed, calls = threading.Event(), []

    class NeverComesBack:
        def __array__(self, *args, **kwargs):
            raise OSError("the copy back failed")

    def failing(x):
        calls.append(1)
        if len(calls) == 4:
            assert retire_failed.wait(LIMIT_S)
            raise PutFailed("the dispatch failed, second")
        y, w = kernel(x)
        return (NeverComesBack() if len(calls) == 2 else y), w

    done, abandoned = [], []

    def abandon(token):
        abandoned.append(token)
        retire_failed.set()

    lane = device_path.DeviceLane(
        deep, dev, failing, 4, lambda token, *rest: done.append(token),
        on_abandon=abandon)
    for k in range(4):
        lane.submit(filler(chunks[k]), CHUNK_BYTES, k)
    lane.close()
    assert isinstance(lane.failure, OSError)
    assert done == [0] and abandoned == [1, 2, 3]


def test_close_drains_the_chunks_in_both_queues(parts, deep):
    """Chunk 0 in the retirer's hands, 1 handed over, 2 in the dispatcher's
    hands, 3 staged: `close()` waits for all four, in order."""
    device_path, _, dev, kernel, chunks = parts
    retire_gate, dispatch_gate = threading.Event(), threading.Event()
    calls, done = [], []

    def held_kernel(x):
        calls.append(1)
        if len(calls) == 3:
            assert dispatch_gate.wait(LIMIT_S)
        return kernel(x)

    def held_done(token, back, word, good):
        if token == 0:
            assert retire_gate.wait(LIMIT_S)
        done.append((token, good))

    threads = threading.active_count()
    lane = device_path.DeviceLane(deep, dev, held_kernel, 4, held_done)
    for k in range(4):
        lane.submit(filler(chunks[k]), CHUNK_BYTES, k)
    deadline = time.monotonic() + LIMIT_S / 2
    while (lane._staged.qsize(), lane._handoff.qsize()) != (1, 1):
        assert time.monotonic() < deadline, "the queues never filled"
        time.sleep(0.001)
    closer = threading.Thread(target=lane.close)
    closer.start()
    closer.join(timeout=0.2)
    assert closer.is_alive() and done == []  # it waits for what is in flight
    dispatch_gate.set()
    retire_gate.set()
    closer.join(timeout=LIMIT_S / 2)
    assert not closer.is_alive()
    assert done == [(k, True) for k in range(4)] and lane.failure is None
    assert threading.active_count() == threads and lane._helpers == []
    assert lane._staged.empty() and lane._handoff.empty()


def slot_frame(sa, nbytes):
    """The frame a slot holds, from where its header starts: (correlation
    id, payload), parsed and crc32c-checked by the C++ framework."""
    start = bytes(sa[:HEADROOM]).rindex(b"TRPC")
    cid, pay, consumed = native.unframe(sa[start:HEADROOM + nbytes])
    assert consumed == HEADROOM - start + nbytes
    return cid, pay


def test_the_fills_crc_is_the_one_in_the_slots_frame(parts):
    """ISSUE 30: `fill(view)` returns the crc32c of what it wrote and the
    framer embeds it unread, so the frame in the slot parses -- `unframe`
    walks the payload against the meta's checksum -- with the submit's
    correlation id and the chunk's bytes; the staging counter moved by the
    bytes."""
    device_path, ring, dev, kernel, chunks = parts
    before = native.staging_counters()
    done = []
    lane = device_path.DeviceLane(
        ring, dev, kernel, DEPTH,
        lambda token, back, word, good: done.append((token, good)))
    for k in range(DEPTH):
        lane.submit(filler(chunks[k]), CHUNK_BYTES, k, 100 + k)
    lane.close()
    assert done == [(k, True) for k in range(DEPTH)]
    for k, sa in enumerate(ring.slots):
        cid, pay = slot_frame(sa, CHUNK_BYTES)
        assert cid == 100 + k
        assert pay.tobytes() == chunks[k].tobytes()
    after = native.staging_counters()
    assert (after["rpc_stage_fused_bytes"] - before["rpc_stage_fused_bytes"]
            == DEPTH * CHUNK_BYTES)


def test_a_crc_that_is_not_the_bytes_is_a_frame_that_does_not_parse(parts):
    """The framer does not look: what `fill` returns is what it embeds."""
    device_path, ring, dev, kernel, chunks = parts
    verdicts = []
    lane = device_path.DeviceLane(
        ring, dev, kernel, 1,
        lambda token, back, word, good: verdicts.append(good))
    lane.submit(lambda view: native.copy_crc32c(view, chunks[0]) ^ 1,
                CHUNK_BYTES, 0)
    lane.close()
    assert verdicts == [False]  # ring.verify holds the D2H to that crc
    with pytest.raises(ValueError, match="corrupt"):
        slot_frame(ring.slots[0], CHUNK_BYTES)


def test_a_fill_that_returns_nothing_is_refused(parts):
    """One contract and no fallback: the lane never asks the framer to
    walk the payload for a fill that brought no crc."""
    device_path, ring, dev, kernel, chunks = parts
    before = native.staging_counters()
    lane = device_path.DeviceLane(ring, dev, kernel, DEPTH,
                                  lambda *a: pytest.fail("nothing launched"))
    with pytest.raises(TypeError):
        lane.submit(lambda view: np.copyto(view.view(np.uint32), chunks[0]),
                    CHUNK_BYTES, 0)
    lane.close()
    assert ring.aborted  # a launch that failed never frees its slot
    assert native.staging_counters() == before


@pytest.mark.parametrize("bad", [0, 2, N_CHUNKS - 1])
def test_a_corrupted_d2h_still_reads_not_good(parts, bad):
    """The guarantee is the one it was: the bytes that came back from the
    device are held to the crc32c in the slot's frame, chunk by chunk."""
    device_path, ring, dev, kernel, chunks = parts
    calls = []

    def one_bit_off(x):
        y, w = kernel(x)
        calls.append(1)
        if len(calls) - 1 == bad:
            y = y.at[7].set(y[7] ^ 1)
        return y, w

    verdicts = []
    lane = device_path.DeviceLane(
        ring, dev, one_bit_off, DEPTH,
        lambda token, back, word, good: verdicts.append((token, good)))
    for k, chunk in enumerate(chunks):
        lane.submit(filler(chunk), CHUNK_BYTES, k, k + 1)
    lane.close()
    assert verdicts == [(k, k != bad) for k in range(N_CHUNKS)]


@pytest.mark.parametrize("nbytes", [16, 24, 4096, 65544, 1048576])
def test_tensor_step_kernel_is_the_reference(cpp_build, nbytes):
    import jax

    from brpc_tpu import device_path

    key = 0xDEADBEEF
    kernel = device_path._tensor_step_kernel(key, "cpu")
    assert kernel.__wrapped__.__name__ == "tensor_step"  # jit_tensor_step
    x = np.random.default_rng(nbytes).integers(0, 256, nbytes,
                                               dtype=np.uint8)
    y, w = kernel(jax.device_put(x.view(np.uint32), jax.devices("cpu")[0]))
    got = np.asarray(y).tobytes() + int(w).to_bytes(4, "little")
    assert got == tensor_reference.step(x, key)
    assert np.asarray(y)[:2].tobytes() == x[:8].tobytes()
