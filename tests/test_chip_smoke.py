"""Tier-1 (seconds, no chip): the plumbing chip_smoke.py stands on.

The smoke itself only passes on the TPU host; what can be held here is
that it refuses everything else, that one failed leg fails the run, that
the compile cache lands where it was placed from outside, and that a
build/ copied from another path is never trusted.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_refuses_a_cpu_and_names_the_missing_tpu():
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr and "JAX_PLATFORMS=cpu" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result, passing or otherwise


def test_refuses_a_directory_that_holds_only_the_script(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=_env(), capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_one_failed_leg_fails_the_run(capsys):
    import chip_smoke

    def good():
        return {"fact": 1}

    def bad():
        raise chip_smoke.LegFailed("the reason")

    def crash():
        raise ValueError("unplanned")

    device = {"platform": "tpu", "kind": "stub", "count": 1}
    assert chip_smoke.run_legs([("a", 5, good)], device) == 0
    capsys.readouterr()
    status = chip_smoke.run_legs(
        [("a", 5, good), ("b", 5, bad), ("c", 5, crash), ("d", 5, good)],
        device)
    assert status != 0
    out, err = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.splitlines()]
    # Every leg reported, each line naming the device it ran beside.
    assert [(ln["leg"], ln["ok"]) for ln in lines] == [
        ("a", True), ("b", False), ("c", False), ("d", True)]
    assert all(ln["platform"] == "tpu" and ln["device_kind"] == "stub"
               and ln["device_count"] == 1 for ln in lines)
    assert lines[1]["error"] == "the reason"
    assert "ValueError" in lines[2]["error"]
    assert "b, c" in err


def test_a_leg_that_overruns_its_limit_fails(capsys):
    import time

    import chip_smoke

    device = {"platform": "tpu", "kind": "stub", "count": 1}
    status = chip_smoke.run_legs([("slow", 1, lambda: time.sleep(30))],
                                 device)
    assert status != 0
    line = json.loads(capsys.readouterr().out)
    assert line["ok"] is False and "timed out" in line["error"]


def _checkout_copy(dst: Path) -> Path:
    """The python side of the checkout, copied: the cache helper anchors
    `.jax_cache` to the checkout ITS file lives in, and the real one fills
    as a side effect of every other jax test."""
    dst.mkdir()
    shutil.copy(REPO / "__graft_entry__.py", dst)
    shutil.copytree(REPO / "brpc_tpu", dst / "brpc_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


_JIT_ENTRY = ("import jax; from __graft_entry__ import entry; "
              "fn, a = entry(); jax.block_until_ready(jax.jit(fn)(*a))")


def test_cache_dir_given_from_outside_is_the_only_one(tmp_path):
    checkout = _checkout_copy(tmp_path / "checkout")
    outside = tmp_path / "outside_cache"
    subprocess.run([sys.executable, "-c", _JIT_ENTRY], cwd=checkout,
                   env=_env(JAX_COMPILATION_CACHE_DIR=str(outside)),
                   check=True, timeout=300)
    assert any(p.name.endswith("-cache") for p in outside.iterdir())
    assert not (checkout / ".jax_cache").exists()


def test_cache_defaults_to_the_checkout(tmp_path):
    checkout = _checkout_copy(tmp_path / "checkout")
    subprocess.run([sys.executable, "-c", _JIT_ENTRY], cwd=checkout,
                   env=_env(), check=True, timeout=300)
    cached = list((checkout / ".jax_cache").iterdir())
    # Small kernels too: the default 1 s floor would have skipped them all.
    assert any(p.name.endswith("-cache") for p in cached)


def test_build_dir_configured_for_another_path_is_reconfigured(tmp_path):
    from brpc_tpu import native

    first = tmp_path / "first"
    first.mkdir()
    (first / "CMakeLists.txt").write_text(
        "cmake_minimum_required(VERSION 3.16)\nproject(tiny NONE)\n"
        "add_custom_target(hello ALL COMMAND ${CMAKE_COMMAND} -E touch "
        "${CMAKE_BINARY_DIR}/built_here)\n")
    native.build(repo=first)
    assert (first / "build" / "built_here").exists()
    # What the chip tool (or any cp -r) makes: the tree at a new path with
    # a build/ that still names the old one.
    second = tmp_path / "second"
    shutil.copytree(first, second)
    (second / "build" / "stale_marker").write_text("from the other path")
    native.build(repo=second)
    cache = (second / "build" / "CMakeCache.txt").read_text()
    assert f"CMAKE_HOME_DIRECTORY:INTERNAL={second}" in cache
    assert not (second / "build" / "stale_marker").exists()
    assert (second / "build" / "built_here").exists()
    # A build/ that IS this checkout's is kept, not rebuilt from nothing.
    (second / "build" / "kept_marker").write_text("same path")
    native.build(repo=second)
    assert (second / "build" / "kept_marker").exists()
