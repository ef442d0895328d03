"""Spans inside the staging-ring pass (ISSUE 25, 26, 32): a small pass on
the CPU backend leaves, per chunk, one `ring.launch` on the calling thread,
one `ring.dispatch` on the dispatch thread and one `ring.retire` on the
completion thread, with the children the issues list; the caller's self
times add up to the passes' wall time and the two helper threads' stay
inside it; the ring of records is bounded; a window cut out of it clips."""
import threading
import time

import numpy as np
import pytest

from brpc_tpu import spans

LAUNCH_CHILDREN = {"ring.acquire", "ring.stage", "ring.frame", "ring.h2d"}
DISPATCH_CHILDREN = {"ring.kernel_dispatch"}
RETIRE_CHILDREN = {"ring.d2h_wait", "ring.verify", "ring.complete"}


@pytest.fixture
def pipeline(cpp_build):
    import jax

    from brpc_tpu import device_path, native

    dev = jax.devices("cpu")[0]
    chunk_bytes, n = 64 << 10, 6
    words = np.arange(n * chunk_bytes // 4, dtype=np.uint32)
    per = chunk_bytes // 4
    chunks = [words[i * per:(i + 1) * per] for i in range(n)]
    touch = device_path._touch_kernel(per, dev.platform)
    ring = native.DeviceStagingRing(3, chunk_bytes + 1024)
    pipe = device_path._ChunkPipeline(ring, chunks, dev, touch, 3, False)
    pipe.run(1)  # compile, first transfers
    spans.clear()
    yield pipe
    ring.close()


def test_every_chunk_has_its_launch_and_retire_with_their_children(pipeline):
    n = len(pipeline.chunks)
    pipeline.run(2)
    assert pipeline.ok
    by_request, threads = {}, {}
    for name, start, end, request, thread in spans.snapshot():
        assert end >= start
        by_request.setdefault(request, []).append((name, start, end))
        threads.setdefault(name, set()).add(thread)
    # The pass over the bytes and the H2D are the caller's, the dispatch a
    # second thread's, the retire side a third's.
    me = threading.get_ident()
    for name in LAUNCH_CHILDREN | {"ring.launch", "ring.pass", "ring.drain"}:
        assert threads[name] == {me}, name
    (dispatcher,) = threads["ring.dispatch"]
    (completions,) = threads["ring.retire"]
    assert len({me, dispatcher, completions}) == 3
    for name in DISPATCH_CHILDREN:
        assert threads[name] == {dispatcher}, name
    for name in RETIRE_CHILDREN:
        assert threads[name] == {completions}, name
    chunk_requests = [r for r in by_request if r[1] is not None]
    assert len(chunk_requests) == 2 * n  # two passes, every chunk
    for request in chunk_requests:
        got = by_request[request]
        names = [name for name, _, _ in got]
        assert names.count("ring.launch") == 1, (request, names)
        assert names.count("ring.dispatch") == 1, (request, names)
        assert names.count("ring.retire") == 1, (request, names)
        # One pass over the bytes and one header write a chunk (ISSUE 30).
        assert names.count("ring.stage") == 1, (request, names)
        assert names.count("ring.frame") == 1, (request, names)
        # The children lie inside their parent's interval (nesting is in
        # the times), and the launch inside its pass's ring.pass. A chunk's
        # three parents follow one another: each is top-level on its thread.
        parents = []
        for parent, children in (("ring.launch", LAUNCH_CHILDREN),
                                 ("ring.dispatch", DISPATCH_CHILDREN),
                                 ("ring.retire", RETIRE_CHILDREN)):
            (p0, p1), = [(s, e) for nm, s, e in got if nm == parent]
            assert {nm for nm, s, e in got
                    if nm != parent and p0 <= s and e <= p1} == children
            parents += [p0, p1]
        assert parents == sorted(parents), (request, parents)
        (l0, l1), = [(s, e) for nm, s, e in got if nm == "ring.launch"]
        assert any(s <= l0 and l1 <= e
                   for _, s, e in by_request[(request[0], None)])
    # Two passes and the drain, each under a ring.pass of that pass; the
    # drain is the last pass's and lies inside a ring.pass of its own.
    passes = [r for r in by_request if r[1] is None]
    assert {p[0] for p in passes} == {c[0] for c in chunk_requests}
    last = by_request[max(passes)]
    (d0, d1), = [(s, e) for nm, s, e in last if nm == "ring.drain"]
    assert any(nm == "ring.pass" and s <= d0 and d1 <= e for nm, s, e in last)
    assert all(nm != "ring.drain" for p in passes if p != max(passes)
               for nm, _, _ in by_request[p])


def test_a_pass_stages_every_byte_fused_and_the_framer_walks_none(pipeline):
    """ISSUE 30's counters over the ring pass: `ring.stage` is the one
    pass over a chunk's bytes (copy + crc32c), `ring.frame` a header and a
    meta from that crc: the framer has no way to read a payload."""
    from brpc_tpu import native

    before = native.staging_counters()
    pipeline.run(3)
    assert pipeline.ok
    after = native.staging_counters()
    assert (after["rpc_stage_fused_bytes"] - before["rpc_stage_fused_bytes"]
            == 3 * len(pipeline.chunks) * pipeline.chunk_bytes)


def test_self_times_and_remainder_add_up_to_the_wall_time(pipeline):
    t0 = time.monotonic()
    for _ in range(5):
        pipeline.run(1)
    t1 = time.monotonic()
    records = spans.snapshot(t0, t1)
    me = threading.get_ident()
    launcher = [r for r in records if r[4] == me]
    helpers = [r for r in records if r[4] != me]
    own = spans.self_times(launcher)
    assert set(own) == LAUNCH_CHILDREN | {"ring.launch", "ring.pass",
                                          "ring.drain"}
    assert all(v >= 0 for v in own.values()), own
    # The launcher's stages plus its remainder (the loop's own: ring.pass
    # and ring.launch self times) and its wait for the last retires are
    # the time inside the passes, which is all of [t0, t1] but the five
    # calls' own overhead.
    assert sum(own.values()) == pytest.approx(t1 - t0, rel=0.02)
    # Self time never counts a moment twice: the top-level spans alone
    # cover the same time.
    top = sum(end - start for name, start, end, *_ in launcher
              if name == "ring.pass")
    assert sum(own.values()) == pytest.approx(top, rel=1e-9)
    # The helper threads' self times are their own clocks: beside the
    # caller's, each side's never more than the wall time, and reduced per
    # thread (over all the records the threads' sums simply add). A lane a
    # run, so a thread's number may be a dispatch thread's in one run and a
    # completion thread's in the next: the sides are told apart by name.
    beside = spans.self_times(helpers)
    dispatch_side = DISPATCH_CHILDREN | {"ring.dispatch"}
    retire_side = RETIRE_CHILDREN | {"ring.retire"}
    assert set(beside) == dispatch_side | retire_side
    for side in (dispatch_side, retire_side):
        assert 0 < sum(beside[name] for name in side) <= t1 - t0
    assert sum(spans.self_times(records).values()) == pytest.approx(
        sum(own.values()) + sum(beside.values()), rel=1e-9)


def test_snapshot_clips_to_the_window_and_the_ring_is_bounded():
    spans.clear()
    with spans.span("outer", request=(1, None)):
        t_in = time.monotonic()
        with spans.span("inner", request=(1, 0)):
            time.sleep(0.002)
    t_out = time.monotonic()
    cut = spans.snapshot(t_in, t_out)
    assert {r[0] for r in cut} == {"outer", "inner"}
    assert all(t_in <= r[1] <= r[2] <= t_out for r in cut)
    assert spans.snapshot(t_out + 1.0, t_out + 2.0) == []
    own = spans.self_times(spans.snapshot())
    assert own["inner"] >= 0.002 and own["outer"] >= 0
    for i in range(spans.CAPACITY + 100):
        with spans.span("flood", request=i):
            pass
    assert spans.CAPACITY >= 1 << 16
    assert len(spans.snapshot()) == spans.CAPACITY
    spans.clear()
