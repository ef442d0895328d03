"""The cell `tensor_echo_1m_c4` (ISSUE 29): its entries resolve, its
reference and its readers' arithmetic on hand-made observations (host-only:
no chip, no build/, no server), and its CPU rehearsal end to end -- as it
stands `correct` is true, with the handler answering from the host
(`--control host_echo`, `--control host_step`) it is false. Of
`BENCHMARK.json` these tests hold names and properties only (benchmark/
README.md, "Adding things"): never a position, a whole list or a count, so
a later cell or metric beside these changes nothing here."""
import json
import os
import subprocess
import sys
import time
import zlib
from pathlib import Path

import numpy as np
import pytest

from benchmark import manifest, payload, reference, stages, tensor_reference
from benchmark import tensor_roofline, xplane

REPO = Path(__file__).resolve().parent.parent.parent
MAN = manifest.load()
CELL = "tensor_echo_1m_c4"
LANE_READERS = ("tensor_device_leg_share", "tensor_take_wait_mean_us",
                "tensor_launch_mean_us", "tensor_reply_mean_us")
# The client's half of the call, from tensor_load.cc's own dumps: each the
# accepted reader of echo_1m_c4's client named beside it, under this cell's
# name (test_benchmark_client_readers.py holds the accepted entries' cells to
# the `served` driver, so the cell cannot be appended to their lists).
CLIENT_READERS = {
    "tensor_client_reply_handoff_mean_us": "tici_reply_handoff_1m_mean_us",
    "tensor_client_cut_mean_us": "tnet_client_cut_1m_mean_us"}
NEW_READERS = LANE_READERS + ("tensor_step_roofline",
                              "device_idle_share.tensor") + tuple(
                                  CLIENT_READERS)
# Accepted readers that find the same spans and stages in this cell as in
# the cell they came with, and read them unchanged.
RING_READERS = ("ring_acquire_wait_share", "ring_h2d_dispatch_share",
                "ring_retire_overlap_share")
STAGE_READERS = ("tnet_consume_to_cut_mean_us", "tnet_write_queue_mean_us",
                 "trpc_server_residence_1m_mean_us")
# Accepted readers the cell does NOT join (PERF.md section 3 says why).
NOT_JOINED = ("ring_stage_frame_share", "ring_verify_share",
              "ring_launcher_rest_share", "ring_vs_raw_ratio",
              "touch_kernel_roofline", "device_idle_share.ring")
KEY = 0x5EED1E57


# ------------------------------------------------------------ the manifest

def test_manifest_still_has_no_problems_and_the_cell_reports_its_metrics():
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, CELL)
    cfg = manifest.config(MAN, cell)
    assert (cfg["driver"], cfg["chips"], cell["traffic"]) == (
        "tensor", 1, "closed_1m_c4")
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert cfg["key"] == KEY and cfg["timeout_ms"] == 10000
    assert cfg["max_retry"] == 0 and cfg["ring_depth"] == 4
    e2e = {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)}
    assert {"goodput_gbps", "p99_us", "setup_s"} <= e2e
    layer = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW_READERS + RING_READERS + STAGE_READERS) <= layer
    assert not layer & set(NOT_JOINED)
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for name in NEW_READERS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "goodput_gbps"
    for name in RING_READERS:
        assert "bulk_64m_ring" in by_name[name]["workloads"]
    for name in STAGE_READERS:
        assert "echo_1m_c4" in by_name[name]["workloads"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_entry_matches_its_reader(name):
    (entry,) = [m for m in MAN["per_layer"] if m["name"] == name]
    reader = manifest.reader(name)
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) \
        == (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    assert callable(reader.read) and reader.read({}) is None


@pytest.mark.parametrize("name", RING_READERS + STAGE_READERS)
def test_an_accepted_entry_the_cell_joins_still_matches_its_reader(name):
    """Every accepted entry the cell is appended to is what it was: its
    reader's four constants, and the end-to-end metric it moves is one the
    cell reports."""
    (entry,) = [m for m in MAN["per_layer"] if m["name"] == name]
    reader = manifest.reader(name)
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) \
        == (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    assert CELL in entry["workloads"]
    assert CELL in next(m for m in MAN["end_to_end"]
                        if m["name"] == entry["moves"])["workloads"]


def test_the_traffic_file_is_echo_1m_c4s():
    assert manifest.cell(MAN, "echo_1m_c4")["traffic"] == manifest.cell(
        MAN, CELL)["traffic"]


# ----------------------------------------------------------- the reference

def test_reference_step_by_hand():
    x = np.array([5, 6, 0, 0xFFFFFFFF], dtype="<u4")
    y = np.array([5, 6, KEY, 0xFFFFFFFF ^ KEY], dtype="<u4")
    w = (5 + 6 * 3 + 0 * 5 + 0xFFFFFFFF * 7) & 0xFFFFFFFF
    assert tensor_reference.step(x.tobytes(), KEY) == (
        y.tobytes() + w.to_bytes(4, "little"))


@pytest.mark.parametrize("nbytes", [0, 8, 12, 20])
def test_reference_refuses_what_the_service_refuses(nbytes):
    with pytest.raises(ValueError):
        tensor_reference.step(bytes(nbytes), KEY)


@pytest.mark.parametrize("nbytes", [16, 4096, 65544])
def test_benchmarks_reference_and_the_programs_agree(nbytes):
    """Two plain references written apart (neither imports the other)."""
    from brpc_tpu import tensor_reference as the_programs

    x = payload.body(2**31 + 5, 3, nbytes)
    assert tensor_reference.step(x, KEY) == the_programs.step(x, KEY)


def test_request_is_the_clients_tag_and_body():
    req = tensor_reference.request(9, 2, 7, 4096)
    assert req[:8] == ((2 << 48) | 7).to_bytes(8, "little")
    assert req[8:] == payload.body(9, 2, 4088) and len(req) == 4096


def sound_report(seed=11, callers=2, nbytes=4096, seqs=(40, 41)):
    return {"attempted": 80, "ok": 80, "rpc_failed": 0, "mismatched": 0,
            "window_s": 2.0, "bytes_each": nbytes,
            "body_crc32": payload.bodies_crc32(seed, callers, nbytes),
            "last_seq": list(seqs),
            "last_reply_crc32": [
                tensor_reference.reply_crc32(seed, c, s, nbytes, KEY)
                for c, s in enumerate(seqs)]}


def test_judge_passes_a_sound_report():
    got = tensor_reference.judge(sound_report(), 11, 2, 4096, KEY)
    assert got == [("replies_wrong", 0, 0),
                   ("replies_missing_or_error", 0, 0),
                   ("digests_wrong", 0, 0)]


@pytest.mark.parametrize("field,number", [
    ("mismatched", "replies_wrong"), ("rpc_failed",
                                      "replies_missing_or_error"),
    ("body_crc32", "digests_wrong"), ("last_reply_crc32", "digests_wrong")])
def test_judge_catches(field, number):
    report = sound_report()
    if field == "last_reply_crc32":
        # The crc of a plain echo of the last request: the chip was skipped.
        report[field][1] = zlib.crc32(tensor_reference.request(11, 1, 41,
                                                               4096))
    else:
        report[field] += 1
    got = dict((n, v) for n, v, _ in tensor_reference.judge(
        report, 11, 2, 4096, KEY))
    assert got[number] == 1


@pytest.mark.parametrize("answered,lane,executions,want", [
    (100, 100, None, 0), (100, 104, None, 0),   # the scrapes bracket more
    (100, 97, None, 3), (100, None, None, 100),  # no counter: all short
    (100, 100, 100, 0), (100, 100, 90, 10), (100, 95, 99, 5),
    (100, 0, 0, 100)])
def test_device_calls_short(answered, lane, executions, want):
    assert tensor_reference.device_calls_short(answered, lane,
                                               executions) == want


def test_tensor_step_counts_one_read_and_one_write():
    assert tensor_roofline.tensor_step_bytes(1 << 20) == 2 << 20
    assert tensor_roofline.tensor_step_least_s(
        1 << 20, "TPU v5 lite") == pytest.approx((2 << 20) / 819e9)
    with pytest.raises(KeyError):
        tensor_roofline.tensor_step_least_s(1 << 20, "TPU v9")


# ------------------------------------------------------------- the readers

def stage_dump(count, sum_us):
    return {"count": count, "sum_us": sum_us, "max_us": 9000,
            "buckets": [[52, count]]}


def served_obs(per_call_us, calls=100, warm=7):
    """Two scrapes `calls` calls apart; the warm-up's samples (before the
    first scrape) are ten times slower and must not show."""
    before = {s: stage_dump(warm, warm * 10 * us)
              for s, us in per_call_us.items()}
    after = {s: stage_dump(warm + calls, warm * 10 * us + calls * us)
             for s, us in per_call_us.items()}
    return {"before": {"status": {"stages": before}, "vars": {}},
            "after": {"status": {"stages": after}, "vars": {}}}


STAGE_US = {"tnet.consume_to_cut": 800, "tfiber.dispatch_to_handler": 10,
            "trpc.handler": 3000, "trpc.respond": 5, "tnet.write_queue": 185,
            "tdev.take_wait": 900, "tdev.reply": 250}


def test_leg_share_is_the_handlers_mean_over_the_residence():
    obs = served_obs(STAGE_US)
    read = manifest.reader("tensor_device_leg_share").read
    assert read(obs) == pytest.approx(100 * 3000 / 4000)
    assert manifest.reader("trpc_server_residence_1m_mean_us").read(
        obs) == pytest.approx(4000)
    assert manifest.reader("tensor_take_wait_mean_us").read(
        obs) == pytest.approx(900)
    # A program without the stages (the parent), an empty window: None.
    assert read({}) is None
    assert read(served_obs({"trpc.handler": 3000})) is None
    empty = served_obs(STAGE_US, calls=0)
    assert read(empty) is None
    assert manifest.reader("tensor_take_wait_mean_us").read(empty) is None


def place_calls(t0, calls, before=True):
    """Hand-placed spans of `calls` served calls inside a window that
    starts at t0 (and of one call before it, which must not count):
    launcher thread 1, completion thread 2."""
    from brpc_tpu import spans

    launch = [("ring.acquire", 10), ("tensor.fill", 100), ("ring.frame", 150),
              ("ring.h2d", 300), ("ring.kernel_dispatch", 450)]
    retire = [("ring.d2h_wait", 700), ("tensor.reply", 400),
              ("ring.complete", 20)]
    starts = ([t0 - 1.0] if before else []) + [
        t0 + 0.01 + 0.005 * i for i in range(calls)]
    for n, base in enumerate(starts):
        for thread, parent, children in ((1, "ring.launch", launch),
                                         (2, "ring.retire", retire)):
            at = base + (0.002 if thread == 2 else 0.0)
            first = at
            for name, us in children:
                spans._ring.append((name, at, at + us * 1e-6, n, thread))
                at += us * 1e-6
            spans._ring.append((parent, first, at + 5e-6, n, thread))
        spans._ring.append(("tensor.take", base - 0.001, base, None, 1))


def test_lane_readers_take_self_time_a_call_inside_the_window():
    from brpc_tpu import spans

    readers = {n: manifest.reader(n).read for n in LANE_READERS}
    spans.clear()
    t0 = time.monotonic()
    obs = dict(served_obs(STAGE_US), t_first_op=t0, window_s=1.0)
    for name in ("tensor_launch_mean_us", "tensor_reply_mean_us"):
        assert readers[name]({}) is None
        assert readers[name](obs) is None  # no span in the window
    # No span: nothing under this name, the C++ stage tdev.reply (250 us
    # here) is another quantity.
    assert readers["tensor_reply_mean_us"](obs) is None
    place_calls(t0, 20)
    assert readers["tensor_launch_mean_us"](obs) == pytest.approx(
        100 + 150 + 300 + 450)
    assert readers["tensor_reply_mean_us"](obs) == pytest.approx(400)
    # The ring cell's own readers on the served call's spans: shares of
    # the window (1 s, 20 calls), and the retire beside the launch.
    for name, us in (("ring_acquire_wait_share", 10),
                     ("ring_h2d_dispatch_share", 300 + 450)):
        assert manifest.reader(name).read(obs) == pytest.approx(
            100 * 20 * us * 1e-6), name
    # Launch 1,015 us from `base`, retire 1,125 us from base + 2,000 us.
    assert manifest.reader("ring_retire_overlap_share").read(obs) == 0.0
    # The ring cell's spans alone (no tensor.fill) are not a served call.
    spans.clear()
    spans._ring.append(("ring.launch", t0 + 0.1, t0 + 0.2, (1, 0), 1))
    spans._ring.append(("ring.h2d", t0 + 0.1, t0 + 0.2, (1, 0), 1))
    assert readers["tensor_launch_mean_us"](obs) is None
    assert readers["tensor_reply_mean_us"](obs) is None
    spans.clear()


@pytest.mark.parametrize("name", sorted(CLIENT_READERS))
def test_a_client_reader_reads_the_clients_window_as_echo_1m_c4s_does(name):
    stage = {"tensor_client_reply_handoff_mean_us": "tici.link_handoff",
             "tensor_client_cut_mean_us": "tnet.consume_to_cut"}[name]
    served = served_obs({stage: 420})
    obs = {"client_before": served["before"],
           "client_after": served["after"]}
    read = manifest.reader(name).read
    assert read(obs) == pytest.approx(420)
    assert read(obs) == manifest.reader(CLIENT_READERS[name]).read(obs)
    # The server's table of the same stage is never read in its place, and
    # a client that sent no table reads nothing.
    assert read(served) is None
    assert read(dict(obs, client_before=None, client_after=None)) is None


TRACE_EVENTS = [
    ("/device:TPU:0", "XLA Modules", "jit_tensor_step(7)", 0.10, 9e-6),
    ("/device:TPU:0", "XLA Ops", "xor_select_fusion", 0.10, 5e-6),
    ("/device:TPU:0", "XLA Ops", "multiply_reduce_fusion", 0.100005, 3e-6),
    ("/device:TPU:0", "XLA Modules", "jit_tensor_step(7)", 0.20, 9e-6),
    ("/device:TPU:0", "XLA Ops", "xor_select_fusion", 0.20, 5e-6),
    ("/device:TPU:0", "XLA Ops", "multiply_reduce_fusion", 0.200005, 3e-6),
    ("/host:CPU", "python", "bench:window", 0.0, 1.0)]


def test_step_roofline_and_idle_share_from_a_hand_made_trace():
    obs = {"trace": xplane.reduce_events(TRACE_EVENTS),
           "bytes_each": 1 << 20, "device_kind": "TPU v5 lite"}
    assert manifest.reader("tensor_step_roofline").read(obs) == \
        pytest.approx(100 * ((2 << 20) / 819e9) / 8e-6)
    assert manifest.reader("device_idle_share.tensor").read(obs) == \
        pytest.approx(100 * (1 - 16e-6))
    from benchmark.drivers import tensor as driver
    assert driver.step_executions(obs["trace"]) == 2
    assert driver.step_executions(None) is None
    assert driver.step_executions(xplane.reduce_events([])) is None


@pytest.mark.parametrize("events,outcome", [
    ([], None),                                   # the CPU rehearsal's trace
    ([("/device:TPU:0", "XLA Modules", "jit_renamed(1)", 0.1, 5e-6),
      ("/device:TPU:0", "XLA Ops", "fusion", 0.1, 4e-6)], LookupError)])
def test_a_trace_without_the_step_is_silent_only_off_the_chip(events,
                                                              outcome):
    obs = {"trace": xplane.reduce_events(events), "bytes_each": 1 << 20,
           "device_kind": "TPU v5 lite"}
    reader = manifest.reader("tensor_step_roofline")
    if outcome is None:
        assert reader.read(obs) is None
        assert reader.read(dict(obs, trace=None)) is None
        assert manifest.reader("device_idle_share.tensor").read(obs) is None
    else:
        with pytest.raises(outcome, match="jit_renamed"):
            reader.read(obs)


def test_gap_notes_say_what_the_host_was_doing():
    from benchmark.drivers import tensor as driver
    from brpc_tpu import spans

    spans.clear()
    t0 = time.monotonic()
    place_calls(t0, 10, before=False)
    notes = driver.gap_notes(t0, t0 + 1.0)
    assert all(k.startswith(xplane.SPAN_PREFIX) for k in notes)
    assert notes["bench:completion_thread_replies(tensor.reply)"] == \
        pytest.approx(10 * 400e-6)
    assert notes[
        "bench:taker_h2d_and_dispatch(ring.h2d+ring.kernel_dispatch)"] == \
        pytest.approx(10 * 750e-6)
    spans.clear()


def test_stops_name_the_stages_and_spans_a_stop_sat_in():
    from benchmark.drivers import tensor as driver
    from brpc_tpu import spans

    spans.clear()
    t0 = time.monotonic()
    place_calls(t0, 10, before=False)
    spans._ring.append(("ring.frame", t0 + 0.5, t0 + 0.611, 99, 1))
    spans._ring.append(("ring.launch", t0 + 0.5, t0 + 0.612, 99, 1))
    after = {"status": {"stages": {
        "tdev.take_wait": stage_dump(10, 9000) | {"max_us": 114041},
        "trpc.respond": stage_dump(10, 50)}}}
    notes = driver.stops(after, t0, t0 + 1.0)
    assert notes["stage_max_us"] == {"tdev.take_wait": 114041}
    assert notes["longest_spans_us"] == [
        ["ring.frame", 111000], ["tensor.take", 1000],
        ["tensor.take", 1000]]
    assert driver.stops({}, t0, t0 + 1.0)["stage_max_us"] == {}
    spans.clear()


def test_the_integrity_word_is_the_ring_cells():
    x = np.frombuffer(payload.body(1, 0, 4096), dtype="<u4")
    assert tensor_reference.step(x.tobytes(), 0)[-4:] == \
        reference.integrity_word(x).to_bytes(4, "little")
    assert stages.RESIDENCE[2] == "trpc.handler"


# ---------------------------------------------------------- the rehearsal

@pytest.fixture(scope="module")
def built(request):
    try:
        return request.getfixturevalue("cpp_build")
    except Exception as e:  # whatever the build raised: nothing to rehearse
        pytest.skip(f"libtpurpc.so cannot be built here: {e}")


def run_cell(*extra, trace=0, seed=2**31 + 99):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    cmd = [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload",
           CELL, "--seed", str(seed), "--seconds", "1", "--trace",
           str(trace), "--rehearsal", "1", "--set", "bytes=65536", *extra]
    proc = subprocess.run(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, timeout=300)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_comes_out_correct(built, trace):
    proc, line = run_cell(trace=trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"      # never a device number
    assert line["not_the_committed_cell"]["rehearsal"] is True
    assert list(line)[-1] == "compared"
    assert {k: v["value"] for k, v in line["compared"].items()} == {
        "replies_wrong": 0, "replies_missing_or_error": 0,
        "digests_wrong": 0, "device_calls_short": 0}
    if trace:
        # Every reader that needs no device plane has a number.
        assert set(LANE_READERS + RING_READERS + STAGE_READERS) | set(
            CLIENT_READERS) <= set(line["metrics"])
        for name, echo_name in CLIENT_READERS.items():
            assert line["metrics"][name]["value"] > 0
            assert echo_name not in line["metrics"]
        assert line["metrics"]["tensor_device_leg_share"]["value"] > 50
        assert line["metrics"]["ring_retire_overlap_share"]["value"] > 0
        gaps = dict(line["breakdown"]["idle_gaps"])
        assert any("tensor.reply" in k for k in gaps)
        assert "setup_s" not in line["metrics"]
    else:
        assert {"goodput_gbps", "p99_us", "setup_s"} <= set(line["metrics"])
        assert line["metrics"]["goodput_gbps"]["value"] > 0
        assert line["metrics"]["p99_us"]["value"] > 0
    assert "correct: True" in proc.stderr.splitlines()[-1]


def test_answering_from_the_host_comes_out_not_correct(built):
    proc, line = run_cell("--control", "host_echo")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False and line["failed"] > 0
    for number in ("replies_wrong", "device_calls_short"):
        got = line["compared"][number]
        assert got["value"] > got["limit"] == 0, number
    assert line["metrics"]["goodput_gbps"]["value"] == 0
    assert "correct: False" in proc.stderr.splitlines()[-1]


def test_the_right_answer_from_the_host_comes_out_not_correct(built):
    """Every reply compares equal, and no step came back from the device:
    the program's counter is not what the replier says of itself."""
    proc, line = run_cell("--control", "host_step")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] > 0
    values = {k: v["value"] for k, v in line["compared"].items()}
    assert values.pop("device_calls_short") == line["attempted"]
    assert set(values.values()) == {0}
    assert "correct: False" in proc.stderr.splitlines()[-1]


def test_an_unknown_control_is_refused(built):
    proc, line = run_cell("--control", "flip_reply")
    assert proc.returncode != 0 and line is None
