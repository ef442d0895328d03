"""`ring_fill_overlap_share` (ISSUE 32) on hand-placed records, as
test_benchmark_overlap_reader.py places them: one thread doing both in turn
reads 0, a launch half inside another thread's dispatch reads 50, a window
without a launch -- or of a program that has no `ring.dispatch` -- reads
None, a record the window clips counts only its inside. Host-only."""
import time

import pytest

from benchmark import manifest

NAME = "ring_fill_overlap_share"
SUBMITTER, DISPATCHER, COMPLETIONS = 1, 2, 3


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


def test_the_reader_is_the_manifests_entry_letter_for_letter():
    man = manifest.load()
    assert manifest.problems(man) == []
    (entry,) = [m for m in man["per_layer"] if m["name"] == NAME]
    reader = manifest.reader(NAME)
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "higher",
        "source": reader.SOURCE, "layer": reader.LAYER,
        "moves": reader.MOVES,
        "workloads": ["bulk_64m_ring", "tensor_echo_1m_c4"]}
    assert (reader.UNIT, reader.SOURCE, reader.MOVES) == (
        "%", "program_span", "goodput_gbps")
    # The staging ring's layer, as the accepted readers of it spell it.
    assert reader.LAYER == manifest.reader("ring_retire_overlap_share").LAYER
    for cell in entry["workloads"]:
        assert NAME in [m["name"] for m in
                        manifest.metrics_of(man, "per_layer", cell)]


def test_no_window_no_spans_or_no_launch_reads_none(placed):
    place, read = placed
    reader = manifest.reader(NAME).read
    assert reader({}) is None and reader({"window_s": 3.0}) is None
    assert read() is None  # no spans at all
    place("ring.dispatch", 0.1, 0.2, DISPATCHER)
    place("ring.retire", 0.0, 0.9, COMPLETIONS)
    assert read() is None  # dispatches, and no ring.launch
    place("ring.launch", 1.5, 1.6, SUBMITTER)
    assert read() is None  # the only launch lies outside the window


def test_a_program_without_the_dispatch_span_reads_none(placed):
    """The parent of ISSUE 32: `ring.launch` holds everything, on one
    thread, and nothing is called `ring.dispatch`."""
    place, read = placed
    for i in range(4):
        place("ring.launch", 0.2 * i, 0.2 * i + 0.1, SUBMITTER)
        place("ring.h2d", 0.2 * i + 0.05, 0.2 * i + 0.07, SUBMITTER)
        place("ring.retire", 0.2 * i + 0.05, 0.2 * i + 0.2, COMPLETIONS)
    assert read() is None
    place("ring.dispatch", 1.2, 1.3, DISPATCHER)  # outside the window
    assert read() is None


def test_one_thread_doing_both_in_turn_reads_zero(placed):
    place, read = placed
    for i in range(4):
        place("ring.launch", 0.2 * i, 0.2 * i + 0.1, SUBMITTER)
        place("ring.dispatch", 0.2 * i + 0.1, 0.2 * i + 0.2, SUBMITTER)
    assert read() == 0.0
    # A launch inside a dispatch of its OWN thread is nesting, not overlap.
    place("ring.dispatch", 0.0, 0.9, SUBMITTER)
    assert read() == 0.0
    # Nor does the completion thread's retire beside it count.
    place("ring.retire", 0.0, 0.9, COMPLETIONS)
    assert read() == 0.0


@pytest.mark.parametrize("dispatches, want", [
    ([(0.10, 0.30)], 50.0),                      # its first half
    ([(0.25, 0.35), (0.35, 0.60)], 75.0),        # two dispatches, back to back
    ([(0.00, 0.90)], 100.0),                     # all of it
    ([(0.50, 0.60)], 0.0),                       # the dispatch came after
])
def test_a_launch_beside_another_threads_dispatch(placed, dispatches, want):
    place, read = placed
    place("ring.launch", 0.2, 0.4, SUBMITTER)
    place("ring.stage", 0.2, 0.3, SUBMITTER)  # children do not count
    for start, end in dispatches:
        place("ring.dispatch", start, end, DISPATCHER)
        place("ring.kernel_dispatch", start, end, DISPATCHER)
    assert read() == pytest.approx(want)


def test_two_dispatchers_at_once_are_counted_once(placed):
    place, read = placed
    place("ring.launch", 0.2, 0.4, SUBMITTER)
    place("ring.dispatch", 0.1, 0.3, DISPATCHER)
    place("ring.dispatch", 0.25, 0.35, 4)
    assert read() == pytest.approx(75.0)


def test_a_record_the_window_clips_counts_only_its_inside(placed):
    place, read = placed
    # 0.2 s of the launch lie inside the window, half of that beside the
    # dispatch; what both did before the window opened is left out.
    place("ring.launch", -0.6, 0.2, SUBMITTER)
    place("ring.dispatch", -0.6, 0.1, DISPATCHER)
    assert read() == pytest.approx(50.0)
    # ... and after it closed.
    place("ring.launch", 0.9, 1.4, SUBMITTER)
    place("ring.dispatch", 0.95, 1.4, DISPATCHER)
    assert read() == pytest.approx(100.0 * (0.1 + 0.05) / (0.2 + 0.1))
