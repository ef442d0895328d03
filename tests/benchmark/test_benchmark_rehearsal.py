"""`benchmark/run.py` end to end, pinned to the CPU on purpose at a tiny
size: each driver once as it stands (the last line's keys, `device` names
the cpu) and once with its timed path broken underneath (each fault the
cell can have: `correct` comes out false). These need `libtpurpc.so`
(the `cpp_build` fixture); the refusal to run without a TPU does not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent.parent
TINY = {
    "bulk_64m_ring": ["--set", "payload_bytes=2097152", "--set",
                      "chunk_kb=256"],
    "echo_4k_c16": ["--set", "callers=4", "--set", "warm_ms=100"],
    "echo_1m_c4": ["--set", "callers=2", "--set", "bytes=65536", "--set",
                   "warm_ms=100"],
}

# The client's half of a served call, which only the client's own report
# can give: a traced line of these cells carries them.
CLIENT_HALF = {
    "echo_4k_c16": {"trpc_issue_mean_us", "tici_reply_handoff_mean_us",
                    "tnet_client_cut_mean_us", "trpc_caller_wake_mean_us"},
    "echo_1m_c4": {"tici_reply_handoff_1m_mean_us",
                   "tnet_client_cut_1m_mean_us"},
}


@pytest.fixture(scope="module")
def built(request):
    """`build/libtpurpc.so` through the suite's one build rule; where it
    cannot be built (no toolchain) the rehearsals skip, they do not fail."""
    try:
        return request.getfixturevalue("cpp_build")
    except Exception as e:  # whatever the build raised: nothing to rehearse
        pytest.skip(f"libtpurpc.so cannot be built here: {e}")


def run_cell(workload, *extra, rehearsal=True, trace=0, seed=2**31 + 77):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    cmd = [sys.executable, str(REPO / "benchmark" / "run.py"),
           "--workload", workload, "--seed", str(seed), "--seconds", "1",
           "--trace", str(trace), *extra]
    if rehearsal:
        cmd += ["--rehearsal", "1", *TINY[workload]]
    proc = subprocess.run(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, timeout=300)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, (json.loads(lines[-1]) if lines else None)


def test_no_tpu_and_no_rehearsal_flag_means_no_result():
    proc, line = run_cell("bulk_64m_ring", rehearsal=False)
    assert proc.returncode != 0 and line is None
    assert "no result" in proc.stderr


@pytest.mark.parametrize("workload,trace", [
    ("bulk_64m_ring", 0), ("bulk_64m_ring", 1), ("echo_4k_c16", 0),
    ("echo_4k_c16", 1), ("echo_1m_c4", 0), ("echo_1m_c4", 1)])
def test_rehearsal_prints_the_contracts_last_line(built, workload,
                                                  trace):
    proc, line = run_cell(workload, trace=trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"      # never a device number
    assert line["not_the_committed_cell"]["rehearsal"] is True
    assert list(line)[-1] == "compared"
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert "setup_s" not in line["metrics"]
        assert "breakdown" in line
        for name in CLIENT_HALF.get(workload, ()):
            assert line["metrics"][name]["value"] > 0
    else:
        assert line["metrics"]["setup_s"]["value"] > 0
        assert len(line["metrics"]) >= 2
    # the numbers compared are also the last lines of standard error
    assert "correct: True" in proc.stderr.splitlines()[-1]


@pytest.mark.parametrize("workload,control,number", [
    ("bulk_64m_ring", "alter_word", "chunks_bytes_wrong"),
    ("bulk_64m_ring", "alter_word", "program_crc32c_failed"),
    ("echo_4k_c16", "flip_reply", "replies_wrong"),
    ("echo_1m_c4", "flip_reply", "replies_wrong")])
def test_a_broken_timed_path_comes_out_not_correct(built, workload,
                                                   control, number):
    proc, line = run_cell(workload, "--control", control)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False and line["failed"] > 0
    got = line["compared"][number]
    assert got["value"] > got["limit"] == 0
    assert "correct: False" in proc.stderr.splitlines()[-1]
