"""`ring_retire_overlap_share` (ISSUE 26) on hand-placed records, as
test_benchmark_stage_readers.py places them: one thread reads 0, a retire
half inside another thread's launch reads 50, a window without a retire
reads None, a record the window clips counts only its inside. Host-only."""
import time

import pytest

from benchmark import manifest

NAME = "ring_retire_overlap_share"
LAUNCHER, COMPLETIONS = 1, 2


@pytest.fixture
def placed():
    """place(name, start, end, thread) relative to t0; read() over the
    first second after t0."""
    from brpc_tpu import spans

    spans.clear()
    t0 = time.monotonic()
    read = manifest.reader(NAME).read

    def place(name, start, end, thread):
        spans._ring.append((name, t0 + start, t0 + end, (1, 0), thread))

    yield place, lambda: read({"t_first_op": t0, "window_s": 1.0})
    spans.clear()


def test_the_reader_is_the_manifests_and_it_has_no_problems():
    man = manifest.load()
    assert manifest.problems(man) == []
    (entry,) = [m for m in man["per_layer"] if m["name"] == NAME]
    reader = manifest.reader(NAME)
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) \
        == (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    assert entry["better"] == "higher"
    assert "bulk_64m_ring" in entry["workloads"]


def test_no_window_no_spans_or_no_retire_reads_none(placed):
    place, read = placed
    reader = manifest.reader(NAME).read
    assert reader({}) is None and reader({"window_s": 3.0}) is None
    assert read() is None  # no spans at all
    place("ring.launch", 0.1, 0.2, LAUNCHER)
    place("ring.pass", 0.0, 0.9, LAUNCHER)
    assert read() is None  # launches, and no ring.retire
    place("ring.retire", 1.5, 1.6, COMPLETIONS)
    assert read() is None  # the only retire lies outside the window


def test_one_thread_doing_both_in_turn_reads_zero(placed):
    place, read = placed
    for i in range(4):
        place("ring.launch", 0.2 * i, 0.2 * i + 0.1, LAUNCHER)
        place("ring.retire", 0.2 * i + 0.1, 0.2 * i + 0.2, LAUNCHER)
    assert read() == 0.0
    # A retire inside a launch of its OWN thread is nesting, not overlap.
    place("ring.retire", 0.02, 0.08, LAUNCHER)
    assert read() == 0.0


@pytest.mark.parametrize("launches, want", [
    ([(0.10, 0.30)], 50.0),                      # its first half
    ([(0.25, 0.35), (0.35, 0.60)], 75.0),        # two launches, back to back
    ([(0.00, 0.90)], 100.0),                     # all of it
    ([(0.50, 0.60)], 0.0),                       # the launch came after
])
def test_a_retire_beside_another_threads_launch(placed, launches, want):
    place, read = placed
    place("ring.retire", 0.2, 0.4, COMPLETIONS)
    place("ring.d2h_wait", 0.2, 0.3, COMPLETIONS)  # children do not count
    for start, end in launches:
        place("ring.launch", start, end, LAUNCHER)
    assert read() == pytest.approx(want)


def test_two_launchers_at_once_are_counted_once(placed):
    place, read = placed
    place("ring.retire", 0.2, 0.4, COMPLETIONS)
    place("ring.launch", 0.1, 0.3, LAUNCHER)
    place("ring.launch", 0.25, 0.35, 3)
    assert read() == pytest.approx(75.0)


def test_a_record_the_window_clips_counts_only_its_inside(placed):
    place, read = placed
    # 0.2 s of the retire lie inside the window, half of that beside the
    # launch; what both did before the window opened is left out.
    place("ring.retire", -0.6, 0.2, COMPLETIONS)
    place("ring.launch", -0.6, 0.1, LAUNCHER)
    assert read() == pytest.approx(50.0)
    # ... and after it closed.
    place("ring.retire", 0.9, 1.4, COMPLETIONS)
    place("ring.launch", 0.95, 1.4, LAUNCHER)
    assert read() == pytest.approx(100.0 * (0.1 + 0.05) / (0.2 + 0.1))
