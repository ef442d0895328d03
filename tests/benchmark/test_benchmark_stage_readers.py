"""The readers of the program's stage clock and ring spans (ISSUE 25), on
hand-made observations: a window is `after - before` and nothing else, an
empty window or a program without the table reads None, never 0. Host-only:
no chip, no build/, no server."""
import time

import pytest

from benchmark import manifest, stages

MAN = manifest.load()
STAGE_READERS = {
    # reader: (stage, what it takes of the window)
    "tici_link_handoff_p50_us": ("tici.link_handoff", "p50"),
    "tici_link_handoff_p99_us": ("tici.link_handoff", "p99"),
    "tnet_write_queue_p99_us": ("tnet.write_queue", "p99"),
    "tfiber_dispatch_to_handler_p99_us": ("tfiber.dispatch_to_handler",
                                          "p99"),
    "tfiber_wake_to_run_p99_us": ("tfiber.wake_to_run", "p99"),
    "trpc_handler_p99_us": ("trpc.handler", "p99"),
    "tnet_consume_to_cut_mean_us": ("tnet.consume_to_cut", "mean"),
    "tnet_write_queue_mean_us": ("tnet.write_queue", "mean"),
}
RESIDENCE_READERS = ("trpc_server_residence_mean_us",
                     "trpc_server_residence_1m_mean_us")
RING_READERS = {
    "ring_acquire_wait_share": ("ring.acquire",),
    "ring_stage_frame_share": ("ring.stage", "ring.frame"),
    "ring_h2d_dispatch_share": ("ring.h2d", "ring.kernel_dispatch"),
    "ring_verify_share": ("ring.verify",),
}
B100, B1000 = 52, 79  # PercentileHistogram::bucket_of(100), (1000)


def stage_dump(count, sum_us, buckets):
    return {"count": count, "sum_us": sum_us, "max_us": 5000,
            "buckets": [list(b) for b in buckets]}


def served_obs(stage, before, after):
    return {"before": {"status": {"stages": {stage: before}}, "vars": {}},
            "after": {"status": {"stages": {stage: after}}, "vars": {}}}


def test_the_new_readers_are_the_manifests_and_it_has_no_problems():
    assert manifest.problems(MAN) == []
    names = {m["name"] for m in MAN["per_layer"]}
    assert set(STAGE_READERS) | set(RING_READERS) | set(
        RESIDENCE_READERS) | {"tfiber_rescued_wakeups"} <= names


def test_bucket_value_is_the_programs():
    # cpp/tvar/percentile.h: exact under 16, slice midpoints above.
    assert [stages.bucket_value(i) for i in (0, 7, 24, 31)] == [0, 7, 8, 15]
    assert stages.bucket_value(B100) == 100  # [96, 104) -> 64 + 8*4 + 4
    assert stages.bucket_value(B1000) == 992


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_reads_the_window_only(name):
    stage, kind = STAGE_READERS[name]
    read = manifest.reader(name).read
    # Warm-up: 1000 slow samples. Window: 98 at ~100 us, 2 at ~1000 us.
    before = stage_dump(1000, 5_000_000, [(B1000, 600), (120, 400)])
    after = stage_dump(1100, 5_011_800,
                       [(B100, 98), (B1000, 602), (120, 400)])
    got = read(served_obs(stage, before, after))
    want = {"mean": 118.0, "p50": 100.0, "p99": 992.0}[kind]
    assert got == pytest.approx(want)
    # An empty window, a table without the stage, a program without the
    # table, another driver's observation: nothing, never a 0.
    assert read(served_obs(stage, after, after)) is None
    assert read(served_obs("other.stage", before, after)) is None
    assert read({"before": {"status": {}}, "after": {"status": {}}}) is None
    assert read({"t_first_op": 1.0, "window_s": 3.0}) is None
    assert read({}) is None


@pytest.mark.parametrize("name", RESIDENCE_READERS)
def test_residence_is_the_sum_of_the_five_server_stages_means(name):
    read = manifest.reader(name).read
    # Window means 10, 20, 30, 40, 50 us over 100 calls each, on top of a
    # warm-up that differs stage by stage.
    before = {s: stage_dump(1000 + i, 7_000 * i, [(B1000, 1000 + i)])
              for i, s in enumerate(stages.RESIDENCE)}
    after = {s: stage_dump(1100 + i, 7_000 * i + 1_000 * (i + 1),
                           [(B100, 100), (B1000, 1000 + i)])
             for i, s in enumerate(stages.RESIDENCE)}
    obs = {"before": {"status": {"stages": before}, "vars": {}},
           "after": {"status": {"stages": after}, "vars": {}}}
    assert read(obs) == pytest.approx(150.0)
    # One of the five missing, an empty window, no table: nothing.
    short = dict(after)
    del short["trpc.respond"]
    assert read({"before": {"status": {"stages": before}},
                 "after": {"status": {"stages": short}}}) is None
    assert read({"before": {"status": {"stages": after}},
                 "after": {"status": {"stages": after}}}) is None
    assert read({"before": {"status": {}}, "after": {"status": {}}}) is None
    assert read({}) is None


def test_rescued_wakeups_sums_every_found_work_counter_over_the_window():
    read = manifest.reader("tfiber_rescued_wakeups").read
    before = {"rpc_scheduler_park_timeouts": 40.0,
              "rpc_scheduler_park_timeouts_found_work": 1.0,
              "rpc_link_credit_wait_timeouts_found_work": 0.0}
    after = {"rpc_scheduler_park_timeouts": 90.0,
             "rpc_scheduler_park_timeouts_found_work": 3.0,
             "rpc_link_credit_wait_timeouts_found_work": 1.0,
             "rpc_socket_epollout_timeouts_found_work": 0.0}
    obs = {"before": {"vars": before}, "after": {"vars": after}}
    assert read(obs) == 3.0
    assert read({"before": {"vars": after}, "after": {"vars": after}}) == 0.0
    # A program without the counters (the parent) reads nothing.
    assert read({"before": {"vars": {"x": 1.0}},
                 "after": {"vars": {"x": 2.0}}}) is None
    assert read({}) is None


@pytest.mark.parametrize("name", sorted(RING_READERS))
def test_ring_reader_takes_self_time_inside_the_window(name):
    from brpc_tpu import spans

    read = manifest.reader(name).read
    assert read({}) is None and read({"window_s": 3.0}) is None
    spans.clear()
    t0 = time.monotonic()
    assert read({"t_first_op": t0, "window_s": 1.0}) is None  # no spans
    # One chunk's spans, hand-placed: a second-long window, in which
    # every child takes 0.05 s; a chunk before the window is left out.
    children = ["ring.acquire", "ring.stage", "ring.frame", "ring.h2d",
                "ring.kernel_dispatch", "ring.d2h_wait", "ring.verify"]
    for base, request in ((t0 - 5.0, (1, 0)), (t0 + 0.1, (2, 0))):
        edge = [base + 0.05 * i for i in range(len(children) + 1)]
        for i, child in enumerate(children):
            spans._ring.append((child, edge[i], edge[i + 1], request, 1))
        spans._ring.append(("ring.launch", edge[0], edge[5], request, 1))
        spans._ring.append(("ring.retire", edge[5], edge[7], request, 1))
    spans._ring.append(("ring.pass", t0 - 1.0, t0 + 2.0, (2, None), 1))
    got = read({"t_first_op": t0, "window_s": 1.0})
    assert got == pytest.approx(5.0 * len(RING_READERS[name]))
    assert stages.ring_self_share({"t_first_op": t0, "window_s": 1.0},
                                  ("ring.launch", "ring.retire")) == \
        pytest.approx(0.0, abs=1e-9)
    spans.clear()
