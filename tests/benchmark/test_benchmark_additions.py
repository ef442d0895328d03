"""The rehearsal of an addition (ISSUE 28): what a later PR may do to the
benchmark -- new files, new entries at the END of a list, its cell's name at
the END of a metric's `workloads` -- is done here to a copy under `tmp_path`
of BENCHMARK.json and of the data and reader files it names. Afterwards the
copy's manifest has no problems, and every accepted entry and file is byte
for byte what it was and where it was. A test that comes with an addition
holds names and properties, as these do; one that held a position or a whole
list of BENCHMARK.json would be what this rehearsal is there to prevent.
Host-only: no chip, no build/, no jax."""
import copy
import json
import shutil
from pathlib import Path

import pytest

from benchmark import manifest

LISTS = ("configs", "workloads", "end_to_end", "per_layer")
RING_LAYER = "staging ring (cpp/tici DeviceStagingRing + " \
             "brpc_tpu/device_path.py)"


def named_files(man: dict) -> list:
    """Every data and reader file BENCHMARK.json names, relative to the
    checkout."""
    files = [Path(c["file"]) for c in man["configs"]]
    files += [manifest.traffic_path(c["traffic"]).relative_to(manifest.ROOT)
              for c in man["workloads"]]
    files += [manifest.reader_path(m["name"]).relative_to(manifest.ROOT)
              for m in man["per_layer"]]
    return sorted(set(files))


@pytest.fixture
def checkout(tmp_path):
    """(root, manifest as accepted) of a copy that holds BENCHMARK.json and
    the files it names, and nothing else."""
    accepted = manifest.load()
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    for rel in named_files(accepted):
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(manifest.ROOT / rel, tmp_path / rel)
    assert manifest.problems(manifest.load(tmp_path), tmp_path) == []
    return tmp_path, accepted


def named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def write_json(path: Path, obj: dict) -> None:
    path.write_text(json.dumps(obj, indent=1) + "\n")


def save(root: Path, man: dict) -> dict:
    write_json(root / "BENCHMARK.json", man)
    return manifest.load(root)


# ---- the additions benchmark/README.md describes, each appended -----------

def add_traffic(root, name, like, **changed):
    mix = dict(json.loads(manifest.traffic_path(like, root).read_text()),
               **changed)
    write_json(manifest.traffic_path(name, root), mix)


def add_config(root, man, name, like, **changed):
    old = named(man["configs"], like)
    cfg = dict(json.loads((root / old["file"]).read_text()), name=name,
               **changed)
    file = str(Path(old["file"]).with_name(name + ".json"))
    write_json(root / file, cfg)
    man["configs"].append(dict(old, name=name, file=file,
                               reduced=cfg["reduced"]))


def add_cell(man, name, config, traffic, joins):
    """The cell, and its name at the end of the `workloads` of every metric
    in `joins`."""
    man["workloads"].append({"name": name, "config": config,
                             "traffic": traffic, "chips": 1,
                             "why": "a rehearsal's cell"})
    for metric in joins:
        named(man["end_to_end"] + man["per_layer"],
              metric)["workloads"].append(name)


def add_layer_metric(root, man, name, cell, moves="goodput_gbps"):
    manifest.reader_path(name, root).write_text(
        f'"""A rehearsal\'s reader."""\nLAYER = "{RING_LAYER}"\n'
        f'UNIT = "%"\nMOVES = "{moves}"\nSOURCE = "program_span"\n\n\n'
        'def read(obs):\n    return obs.get("rehearsed")\n')
    man["per_layer"].append({
        "name": name, "unit": "%", "better": "lower",
        "source": "program_span", "layer": RING_LAYER, "moves": moves,
        "workloads": [cell]})


def a_traffic_mix_with_its_cell(root, man):
    add_traffic(root, "closed_4k_c64", like="closed_4k_c16", callers=64)
    add_cell(man, "echo_4k_c64", "brpc_echo_shm", "closed_4k_c64",
             joins=["p99_us", "qps", "client_cpu_us_per_op",
                    "trpc_issue_mean_us"])


def a_configuration_with_a_cut_and_its_cell(root, man):
    add_config(root, man, "brpc_echo_shm_small_pool", like="brpc_echo_shm",
               reduced=["pool_region_bytes"])
    add_cell(man, "echo_1m_small_pool_c4", "brpc_echo_shm_small_pool",
             "closed_1m_c4", joins=["goodput_gbps", "tici_desc_share"])


def a_cell_joining_the_rings_overlap_metric(root, man):
    add_traffic(root, "ring_64m_d4_c4m", like="ring_64m_d4_c1m",
                chunk_kb=4080)
    add_cell(man, "bulk_64m_ring_c4m", "ici_performance_1chip",
             "ring_64m_d4_c4m",
             joins=["goodput_gbps", "ring_retire_overlap_share"])


def a_per_layer_metric_with_its_reader(root, man):
    add_layer_metric(root, man, "ring_complete_share", "bulk_64m_ring")


def all_of_them_in_one_pr(root, man):
    """One configuration, one traffic file, one cell of the two that joins
    the end-to-end metrics it reports and `ring_retire_overlap_share`, one
    per-layer metric of that cell with its reader."""
    add_config(root, man, "tensor_like_1chip", like="ici_performance_1chip",
               reduced=["payload_bytes"])
    add_traffic(root, "ring_16m_d4_c1m", like="ring_64m_d4_c1m",
                payload_bytes=16777216)
    add_cell(man, "tensor_like_16m", "tensor_like_1chip", "ring_16m_d4_c1m",
             joins=["goodput_gbps", "ring_retire_overlap_share"])
    add_layer_metric(root, man, "tensor_like_share", "tensor_like_16m")


ADDITIONS = [a_traffic_mix_with_its_cell,
             a_configuration_with_a_cut_and_its_cell,
             a_cell_joining_the_rings_overlap_metric,
             a_per_layer_metric_with_its_reader, all_of_them_in_one_pr]


def what_moved(accepted: dict, now: dict) -> list:
    """Everything of the accepted manifest that is not byte for byte what
    and where it was in `now`; an accepted metric's `workloads` may have
    grown at its end."""
    moved = [f"key {key!r}" for key in accepted
             if key not in LISTS and json.dumps(accepted[key])
             != json.dumps(now.get(key))]
    for key in LISTS:
        for i, old in enumerate(accepted[key]):
            new = copy.deepcopy(now[key][i]) if i < len(now[key]) else None
            if new and "workloads" in old:
                new["workloads"] = new["workloads"][:len(old["workloads"])]
            if json.dumps(old) != json.dumps(new):
                moved.append(f"{key}[{i}] {old['name']}")
    return moved


@pytest.mark.parametrize("add", ADDITIONS, ids=lambda f: f.__name__)
def test_an_appended_addition_resolves_and_moves_nothing(checkout, add):
    root, accepted = checkout
    man = copy.deepcopy(accepted)
    add(root, man)
    now = save(root, man)
    assert manifest.problems(now, root) == []
    assert what_moved(accepted, now) == []
    # the new entries are there, by name, and resolve under the copy
    for key in LISTS:
        names = [e["name"] for e in now[key]]
        assert names[:len(accepted[key])] == [e["name"]
                                              for e in accepted[key]]
    for cell in now["workloads"]:
        cfg = manifest.config(now, cell, root)
        assert cfg["reduced"] == named(now["configs"],
                                       cell["config"])["reduced"]
        assert manifest.traffic(cell, root)["loop"] == "closed"
    for metric in now["per_layer"]:
        assert manifest.reader(metric["name"], root).read({}) is None
    # no file that was there changed, in the copy or in the checkout
    for rel in named_files(accepted):
        assert (root / rel).read_bytes() == (manifest.ROOT
                                             / rel).read_bytes()
    assert manifest.load() == accepted


def test_the_new_cell_reports_what_it_joined(checkout):
    root, accepted = checkout
    man = copy.deepcopy(accepted)
    all_of_them_in_one_pr(root, man)
    now = save(root, man)
    e2e = {m["name"] for m in
           manifest.metrics_of(now, "end_to_end", "tensor_like_16m")}
    layer = {m["name"] for m in
             manifest.metrics_of(now, "per_layer", "tensor_like_16m")}
    assert e2e == {"goodput_gbps", "setup_s"}
    assert layer == {"ring_retire_overlap_share", "tensor_like_share"}
    # ... and the accepted cells report exactly what they did
    for cell in accepted["workloads"]:
        for kind in ("end_to_end", "per_layer"):
            assert manifest.metrics_of(now, kind, cell["name"]) == [
                named(now[kind], m["name"]) for m in
                manifest.metrics_of(accepted, kind, cell["name"])]
    assert manifest.reader("tensor_like_share", root).read(
        {"rehearsed": 12.5}) == 12.5


def _put_in_front(root, man):
    add_layer_metric(root, man, "ring_complete_share", "bulk_64m_ring")
    man["per_layer"].insert(0, man["per_layer"].pop())


def _joined_in_front(root, man):
    a_cell_joining_the_rings_overlap_metric(root, man)
    joined = named(man["per_layer"], "ring_retire_overlap_share")
    joined["workloads"].insert(0, joined["workloads"].pop())


def _changed_an_accepted_entry(root, man):
    named(man["end_to_end"], "qps")["bound"] = 0.2


def _retired_an_accepted_entry(root, man):
    man["per_layer"].remove(named(man["per_layer"], "ring_verify_share"))


@pytest.mark.parametrize("edit", [
    _put_in_front, _joined_in_front, _changed_an_accepted_entry,
    _retired_an_accepted_entry], ids=lambda f: f.__name__.strip("_"))
def test_an_edit_to_what_was_there_is_seen(checkout, edit):
    root, accepted = checkout
    man = copy.deepcopy(accepted)
    edit(root, man)
    assert what_moved(accepted, save(root, man))


def _forgot_the_traffic_file(root, man):
    add_cell(man, "echo_4k_c64", "brpc_echo_shm", "closed_4k_c64",
             joins=["p99_us", "qps", "client_cpu_us_per_op"])


def _forgot_the_reader_file(root, man):
    a_per_layer_metric_with_its_reader(root, man)
    manifest.reader_path("ring_complete_share", root).unlink()


def _forgot_the_configuration_file(root, man):
    a_configuration_with_a_cut_and_its_cell(root, man)
    (root / named(man["configs"], "brpc_echo_shm_small_pool")["file"]).unlink()


def _joined_no_per_layer_metric(root, man):
    add_traffic(root, "closed_4k_c64", like="closed_4k_c16", callers=64)
    add_cell(man, "echo_4k_c64", "brpc_echo_shm", "closed_4k_c64",
             joins=["p99_us", "qps"])


def _the_reader_disagrees_with_its_entry(root, man):
    a_per_layer_metric_with_its_reader(root, man)
    named(man["per_layer"], "ring_complete_share")["unit"] = "us"


@pytest.mark.parametrize("add", [
    _forgot_the_traffic_file, _forgot_the_reader_file,
    _forgot_the_configuration_file, _joined_no_per_layer_metric,
    _the_reader_disagrees_with_its_entry],
    ids=lambda f: f.__name__.strip("_"))
def test_a_half_made_addition_is_a_problem_of_the_copy(checkout, add):
    """The files are looked for under the root given, not in the checkout
    the test runs from."""
    root, accepted = checkout
    man = copy.deepcopy(accepted)
    add(root, man)
    assert manifest.problems(save(root, man), root)
    assert manifest.problems(accepted) == []
