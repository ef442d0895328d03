"""`kv_reply_parts_shared_share` on hand-made scrapes: the share of the reply
parts written in the window that lay in the shared region, from the two
counters' window differences. None where no part was written, or from a
program without the counters; a program whose parts all fall back to
process memory reads 0.0. Of `BENCHMARK.json` this holds names and
properties only, never a whole list. Host-only."""
import pytest

from benchmark import manifest

NAME = "kv_reply_parts_shared_share"
CELL = "kv_reuse_9m_c4"


def obs(before, after):
    """An observation whose bracketing scrapes hold these /vars."""
    return {"before": {"vars": before}, "after": {"vars": after}}


def parts(shared, local):
    return {"rpc_reply_parts_shared": shared, "rpc_reply_parts_local": local}


def test_the_reader_is_the_manifests_entry():
    man = manifest.load()
    assert manifest.problems(man) == []
    (entry,) = [m for m in man["per_layer"] if m["name"] == NAME]
    reader = manifest.reader(NAME)
    assert (entry["unit"], entry["better"], entry["source"]) == (
        "%", "higher", "program_counter")
    assert (reader.UNIT, reader.SOURCE, reader.MOVES) == (
        entry["unit"], entry["source"], entry["moves"])
    assert entry["moves"] == "goodput_gbps"
    # The pool's layer, as the accepted readers of it spell it.
    assert reader.LAYER == entry["layer"] == manifest.reader(
        "tici_desc_share").LAYER
    assert CELL in entry["workloads"]
    assert NAME in [m["name"] for m in
                    manifest.metrics_of(man, "per_layer", CELL)]


@pytest.mark.parametrize("before, after, want", [
    (parts(10, 5), parts(110, 5), 100.0),     # every part of the window shared
    (parts(0, 0), parts(0, 10980), 0.0),      # every part bounce-copied
    (parts(3, 1), parts(93, 11), 90.0),       # 90 shared, 10 local
    (parts(0, 0), parts(1, 3), 25.0),
], ids=["all_shared", "all_local", "ninety", "quarter"])
def test_the_share_is_of_the_window_differences(before, after, want):
    assert manifest.reader(NAME).read(obs(before, after)) == pytest.approx(
        want)


def test_a_counter_first_seen_after_the_window_opened_counts_from_zero():
    assert manifest.reader(NAME).read(
        obs({}, parts(30, 10))) == pytest.approx(75.0)


@pytest.mark.parametrize("observation", [
    {},                                                 # no scrapes at all
    {"before": None, "after": None},
    obs(parts(5, 5), parts(5, 5)),                      # no part written
    obs({}, {"rpc_reply_parts_shared": 4}),             # one counter only
    obs({}, {"rpc_kv_gets": 7}),                        # a program without
], ids=["no_scrapes", "none", "no_part", "one_counter", "no_counters"])
def test_nothing_to_read_reads_none(observation):
    assert manifest.reader(NAME).read(observation) is None
