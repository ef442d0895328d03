"""BENCHMARK.json resolves to files by name, and keeps the contract's
naming rules. Every assertion here holds an entry by its name or a property
of every entry, never a position, a whole list or a count: a later PR
appends entries, and these tests must still pass (test_benchmark_additions.py
rehearses that). Host-only: no chip, no build/, no jax."""
import copy
import importlib
import json

import pytest

from benchmark import manifest

MAN = manifest.load()
CELLS = [c["name"] for c in MAN["workloads"]]
LAYER_METRICS = [m["name"] for m in MAN["per_layer"]]


def test_manifest_has_no_problems():
    assert manifest.problems(MAN) == []


def test_manifest_keys_are_exactly_the_contracts():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024
    assert [m["name"] for m in MAN["end_to_end"]].count("setup_s") == 1


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_config_traffic_and_driver(name):
    cell = manifest.cell(MAN, name)
    cfg = manifest.config(MAN, cell)
    traffic = manifest.traffic(cell)
    assert cfg["name"] == cell["config"] and cfg["chips"] == cell["chips"]
    assert traffic["loop"] == "closed"  # no cell offers load faster than replies return
    driver = importlib.import_module("benchmark.drivers." + cfg["driver"])
    assert callable(driver.run)
    assert cfg["guarantees"] and "assumed" in cfg
    # every cut of scale is listed, in the file and in its entry alike
    (entry,) = [c for c in MAN["configs"] if c["name"] == cell["config"]]
    assert isinstance(cfg["reduced"], list)
    assert all(isinstance(k, str) and k for k in cfg["reduced"])
    assert cfg["reduced"] == entry["reduced"]
    reported = {m["name"] for m in
                manifest.metrics_of(MAN, "end_to_end", name)}
    assert "setup_s" in reported and len(reported) >= 2


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_layer_metric_has_a_reader_that_agrees_with_the_manifest(name):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    mod = manifest.reader(name)
    assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
        entry["layer"], entry["unit"], entry["moves"], entry["source"])
    # Nothing to read -> nothing returned (never a 0 standing for a share).
    assert mod.read({}) is None
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def _mutations():
    def unknown_moves(m):
        _named(m["per_layer"], "client_cpu_us_per_op")["moves"] = "nothing"

    def bad_unit(m):
        _named(m["end_to_end"], "p99_us")["unit"] = "us per op"

    def bad_name(m):
        _named(m["workloads"], "echo_4k_c16")["name"] = "echo 4k"

    def missing_traffic(m):
        _named(m["workloads"], "echo_4k_c16")["traffic"] = "no_such_mix"

    def duplicate(m):
        _named(m["per_layer"], "server_cpu_us_per_op")["name"] = \
            "client_cpu_us_per_op"

    def too_many_four_chip_cells(m):
        for c in m["workloads"]:
            c["chips"] = 4

    def moves_a_metric_the_cell_lacks(m):
        # echo_4k_c16 reports qps and p99_us, no goodput_gbps
        _named(m["per_layer"], "client_cpu_us_per_op")["moves"] = \
            "goodput_gbps"

    def loose_bound(m):
        _named(m["end_to_end"], "qps")["bound"] = 0.5

    def unused_config(m):
        m["configs"].append(dict(_named(m["configs"], "brpc_echo_shm"),
                                 name="spare"))

    def one_pair_two_cells(m):
        m["workloads"].append(dict(_named(m["workloads"], "echo_1m_c4"),
                                   name="echo_1m_c4_again"))

    def no_reader_file(m):
        m["per_layer"].append(dict(
            _named(m["per_layer"], "client_cpu_us_per_op"),
            name="a_metric_without_a_file"))

    return [unknown_moves, bad_unit, bad_name, missing_traffic, duplicate,
            too_many_four_chip_cells, moves_a_metric_the_cell_lacks,
            loose_bound, unused_config, one_pair_two_cells, no_reader_file]


@pytest.mark.parametrize("mutate", _mutations(), ids=lambda f: f.__name__)
def test_a_broken_manifest_is_found(mutate):
    man = copy.deepcopy(MAN)
    mutate(man)
    assert manifest.problems(man)
