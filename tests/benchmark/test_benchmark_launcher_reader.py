"""`ring_launcher_rest_share` (ISSUE 28) on hand-placed records, as
test_benchmark_overlap_reader.py places them: the self time of the
launcher's `ring.pass`, `ring.launch` and `ring.drain`, so that with the
three shares of its named stages the launcher's four add up to the window.
What another thread does (`ring.retire` and its children) is not in it.
Host-only."""
import time

import pytest

from benchmark import manifest

NAME = "ring_launcher_rest_share"
LAUNCHERS_FOUR = (NAME, "ring_acquire_wait_share", "ring_stage_frame_share",
                  "ring_h2d_dispatch_share")
LAUNCHER, COMPLETIONS = 1, 2
STAGES = ("ring.acquire", "ring.stage", "ring.frame", "ring.h2d",
          "ring.kernel_dispatch")


@pytest.fixture
def placed():
    """place(name, start, end, thread) relative to t0; read(name) over the
    first second after t0."""
    from brpc_tpu import spans

    spans.clear()
    t0 = time.monotonic()

    def place(name, start, end, thread=LAUNCHER):
        spans._ring.append((name, t0 + start, t0 + end, (1, 0), thread))

    def read(name=NAME):
        return manifest.reader(name).read({"t_first_op": t0,
                                           "window_s": 1.0})

    yield place, read
    spans.clear()


def place_chunk(place, start, stage_s, slack_s):
    """One chunk's launch: the five stages back to back, `slack_s` of the
    launch's own time after them. Returns the launch's end."""
    edge = start
    for stage in STAGES:
        place(stage, edge, edge + stage_s)
        edge += stage_s
    place("ring.launch", start, edge + slack_s)
    return edge + slack_s


def test_the_reader_is_the_manifests():
    man = manifest.load()
    (entry,) = [m for m in man["per_layer"] if m["name"] == NAME]
    reader = manifest.reader(NAME)
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) \
        == (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    assert entry["better"] == "lower" and entry["moves"] == "goodput_gbps"
    assert "bulk_64m_ring" in entry["workloads"]
    # the launcher's four sit in one layer, under its name letter for letter
    layers = {m["layer"] for m in man["per_layer"]
              if m["name"] in LAUNCHERS_FOUR}
    assert layers == {entry["layer"]}


def test_no_window_or_no_spans_reads_none(placed):
    _, read = placed
    reader = manifest.reader(NAME).read
    assert reader({}) is None and reader({"window_s": 3.0}) is None
    assert read() is None  # a window and no spans at all


def test_it_is_the_self_time_of_pass_launch_and_drain(placed):
    place, read = placed
    place("ring.pass", 0.0, 1.0)
    end = place_chunk(place, 0.10, 0.04, 0.02)      # 0.10 .. 0.32
    end = place_chunk(place, end + 0.03, 0.04, 0.02)  # 0.35 .. 0.57
    place("ring.drain", 0.80, 0.95)
    # launch's own 2 x 0.02, drain 0.15, the pass's own 1.0 - 0.44 - 0.15
    assert read() == pytest.approx(100.0 * (0.04 + 0.15 + 0.41))
    assert read("ring_acquire_wait_share") == pytest.approx(8.0)


def test_another_threads_retire_does_not_count(placed):
    place, read = placed
    place("ring.pass", 0.0, 1.0)
    place_chunk(place, 0.1, 0.1, 0.0)
    alone = read()
    assert alone == pytest.approx(50.0)
    # The completion thread retires beside the launcher: nothing moves.
    place("ring.retire", 0.2, 0.9, COMPLETIONS)
    place("ring.d2h_wait", 0.2, 0.6, COMPLETIONS)
    place("ring.verify", 0.6, 0.8, COMPLETIONS)
    place("ring.complete", 0.8, 0.9, COMPLETIONS)
    assert read() == pytest.approx(alone)
    # At depth 1 the launcher retires inline: that time is the retire's,
    # not the pass's own.
    place("ring.retire", 0.7, 0.9, LAUNCHER)
    assert read() == pytest.approx(30.0)


@pytest.mark.parametrize("stage_s, slack_s, gap_s, drain_s", [
    (0.02, 0.00, 0.00, 0.0), (0.03, 0.01, 0.02, 0.1), (0.01, 0.05, 0.0, 0.3)])
def test_the_launchers_four_add_up_to_the_window(placed, stage_s, slack_s,
                                                 gap_s, drain_s):
    place, read = placed
    # One pass covers the window and outlasts it on both sides; the
    # completion thread works beside it all the time.
    place("ring.pass", -0.5, 1.5)
    place("ring.retire", -0.5, 1.5, COMPLETIONS)
    edge = -0.2
    while edge < 1.0 - drain_s:
        edge = place_chunk(place, edge, stage_s, slack_s) + gap_s
    if drain_s:
        place("ring.drain", edge, 1.2)
    shares = {name: read(name) for name in LAUNCHERS_FOUR}
    assert sum(shares.values()) == pytest.approx(100.0)
    assert all(0.0 <= share < 100.0 for share in shares.values())


def test_a_record_the_window_clips_counts_only_its_inside(placed):
    place, read = placed
    place("ring.drain", -0.4, 0.1)
    place("ring.launch", 0.9, 1.6)
    assert read() == pytest.approx(20.0)
