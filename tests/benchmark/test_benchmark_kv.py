"""The cell `kv_put_9m_c4` (ISSUE 33): its entries resolve, the cache
hand-off's reference and its readers' arithmetic on hand-made observations
(host-only: no chip, no build/, no server), and its CPU rehearsal end to
end -- as it stands `correct` is true, with the acknowledgements made on
the host (`--control host_ack`) or the put step writing one slot on
(`--control wrong_slot`) it is false. Of `BENCHMARK.json` these tests hold
names and properties only (benchmark/README.md, "Adding things"): never a
position, a whole list or a count, so a later cell or metric beside these
changes nothing here."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from benchmark import kv_reference, kv_roofline, kv_spans, manifest, payload
from benchmark import reference, xplane

REPO = Path(__file__).resolve().parent.parent.parent
MAN = manifest.load()
CELL = "kv_put_9m_c4"
SPAN_READERS = ("kv_fill_chunk_mean_us", "kv_h2d_dispatch_chunk_mean_us",
                "kv_join_mean_us", "kv_reply_mean_us")
STAGE_READERS = ("kv_device_leg_share", "kv_take_wait_mean_us")
NEW_READERS = SPAN_READERS + STAGE_READERS + (
    "kv_pool_resident_share", "kv_put_step_roofline", "device_idle_share.kv")
# Accepted readers that find the same spans and stages in this cell as in
# the cells they came with, and read them unchanged.
JOINED = ("ring_acquire_wait_share", "ring_h2d_dispatch_share",
          "tnet_consume_to_cut_mean_us", "tnet_write_queue_mean_us")
# Accepted readers the cell does NOT join (PERF.md section 3 says why).
NOT_JOINED = ("ring_fill_overlap_share", "ring_stage_frame_share",
              "ring_verify_share",
              "ring_launcher_rest_share", "ring_vs_raw_ratio",
              "touch_kernel_roofline", "tensor_step_roofline",
              "tensor_launch_mean_us", "trpc_server_residence_1m_mean_us",
              "device_idle_share.ring", "device_idle_share.tensor")


# ------------------------------------------------------------ the manifest

def test_manifest_has_no_problems_and_the_cell_reports_its_metrics():
    assert manifest.problems(MAN) == []
    cell = manifest.cell(MAN, CELL)
    cfg = manifest.config(MAN, cell)
    assert (cfg["driver"], cfg["chips"], cell["traffic"], cell["chips"]) == (
        "kvcache", 1, "closed_9m_c4_l61", 1)
    assert cfg["reduced"] == [] and cfg["architecture"] is None
    assert cfg["timeout_ms"] == 10000 and cfg["max_retry"] == 0
    assert cfg["ring_depth"] == 4 and cfg["pool_sessions"] == 22
    (entry,) = [c for c in MAN["configs"] if c["name"] == cell["config"]]
    assert entry["reduced"] == [] and "Mooncake" in entry["source"]
    e2e = {m["name"] for m in manifest.metrics_of(MAN, "end_to_end", CELL)}
    assert {"goodput_gbps", "p99_us", "setup_s"} <= e2e
    layer = {m["name"] for m in manifest.metrics_of(MAN, "per_layer", CELL)}
    assert set(NEW_READERS + JOINED) <= layer
    assert not layer & set(NOT_JOINED)
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "goodput_gbps"


def test_the_configuration_states_the_published_widths_and_the_pool():
    """1,152 B a token a layer, 61 layers, 8,192 tokens, 22 sessions: the
    traffic file's bytes and layers are the configuration's widths, and the
    pool is what the issue sized."""
    cfg = manifest.config(MAN, manifest.cell(MAN, CELL))
    tr = manifest.traffic(manifest.cell(MAN, CELL))
    w = cfg["widths"]
    assert (w["kv_lora_rank"] + w["qk_rope_head_dim"]) * w[
        "cache_dtype_bytes"] == w["bytes_a_token_a_layer"] == 1152
    assert w["prompt_tokens"] * 1152 == w["layer_bytes"] == tr["bytes"]
    assert w["num_hidden_layers"] == tr["layers"] == 61
    assert w["layer_bytes"] * 61 == w["prompt_bytes"]
    assert cfg["pool_sessions"] * 61 * tr["bytes"] == 12078 << 20
    assert (tr["loop"], tr["callers"], tr["warm_ms"]) == ("closed", 4, 500)
    assert set(cfg["guarantees"]) == {
        "acknowledged_put_is_in_the_pool",
        "eviction_is_whole_and_oldest_first", "word_made_on_the_device",
        "every_call_answered"}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_entry_matches_its_reader(name):
    (entry,) = [m for m in MAN["per_layer"] if m["name"] == name]
    reader = manifest.reader(name)
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) \
        == (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    assert callable(reader.read) and reader.read({}) is None


@pytest.mark.parametrize("name", JOINED)
def test_an_accepted_entry_the_cell_joins_still_matches_its_reader(name):
    (entry,) = [m for m in MAN["per_layer"] if m["name"] == name]
    reader = manifest.reader(name)
    assert (entry["layer"], entry["unit"], entry["moves"], entry["source"]) \
        == (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE)
    assert CELL in entry["workloads"]
    assert "tensor_echo_1m_c4" in entry["workloads"]  # the same lane's cell
    assert CELL in next(m for m in MAN["end_to_end"]
                        if m["name"] == entry["moves"])["workloads"]


# ----------------------------------------------------------- the reference

def test_word_is_the_ring_cells_integrity_word():
    x = np.array([5, 6, 0, 0xFFFFFFFF], dtype="<u4")
    assert kv_reference.word(x.tobytes()) == (
        5 + 6 * 3 + 0 * 5 + 0xFFFFFFFF * 7) & 0xFFFFFFFF
    body = payload.body(1, 0, 4096)
    assert kv_reference.word(body) == reference.integrity_word(
        np.frombuffer(body, dtype="<u4"))


@pytest.mark.parametrize("nbytes", [24, 4096, 65536])
def test_benchmarks_reference_and_the_programs_agree(nbytes):
    """Two plain references written apart (neither imports the other)."""
    from brpc_tpu import kv_reference as the_programs

    x = kv_reference.request(2**31 + 5, 77, 3, 2, 9, nbytes)
    assert len(x) == nbytes
    assert kv_reference.word(x) == the_programs.word(x)
    assert kv_reference.NOT_FOUND == 2


def test_request_is_the_clients_stamp_and_body():
    req = kv_reference.request(9, 2**63 + 7, 60, 3, 2**24 + 5, 4096)
    assert req[:8] == (2**63 + 7).to_bytes(8, "little")
    assert req[8:12] == (60).to_bytes(4, "little")
    assert req[12:16] == ((3 << 24) | 5).to_bytes(4, "little")
    assert req[16:] == payload.body(9, 3, 4080)


def test_session_ids_are_seeded_and_unique_per_caller():
    ids = {kv_reference.session_id(2**31 + 11, 4, c, n)
           for c in range(4) for n in range(1, 50)}
    assert len(ids) == 4 * 49
    sid = kv_reference.session_id(2**31 + 11, 4, 3, 17)
    assert ((sid >> 32) & 0xFF, sid & 0xFFFFFFFF) == (3, 17)
    assert sid != kv_reference.session_id(2**31 + 12, 4, 3, 17)


def test_the_programs_reference_evicts_as_the_replay_does():
    """The benchmark's replay of admission numbers and the program's plain
    cache agree on who is resident, through evictions and a re-admission."""
    from brpc_tpu import kv_reference as the_programs

    ref = the_programs.Cache(3, 2, 16)
    seen = {}
    for session in (5, 6, 7, 5, 8, 9, 5, 6):  # 5 and 6 come back evicted
        _, admitted = ref.put(session, 0, bytes(16))
        seen[session, admitted] = None
    # A session that came back is a new tenancy under its old id: the
    # client's ids never repeat, so the replay takes (id, admitted) pairs
    # with distinct ids; here the evicted tenancies are told apart by hand.
    pairs = [(f"{s}@{a}", a) for s, a in seen]
    resident, evicted, problems = kv_reference.resident_after(pairs, 3)
    assert problems == 0
    assert {int(r.split("@")[0]) for r in resident} == set(ref.slots)
    assert len(evicted) == len(pairs) - 3
    assert [int(e.split("@")[1]) for e in evicted] == list(range(
        len(pairs) - 3))


@pytest.mark.parametrize("pairs, slots, want", [
    ([], 2, (set(), [], 0)),
    ([(7, 0), (8, 1)], 2, ({7, 8}, [], 0)),
    ([(9, 2), (7, 0), (8, 1)], 2, ({8, 9}, [7], 0)),
    ([(7, 0), (8, 2)], 2, ({7, 8}, [], 1)),      # an admission is missing
    ([(7, 0), (8, 0)], 2, ({7, 8}, [], 1)),      # one number, two sessions
    ([(7, 0), (7, 1)], 1, ({7}, [7], 1)),        # one session, two numbers
])
def test_resident_after(pairs, slots, want):
    assert kv_reference.resident_after(pairs, slots) == want


def sound_report(seed=2**31 + 3, callers=2, nbytes=4096):
    last = []
    for c in range(callers):
        session = kv_reference.session_id(seed, callers, c, 5)
        x = kv_reference.request(seed, session, 2, c, 40 + c, nbytes)
        last.append([session, 2, 40 + c, kv_reference.word(x)])
    return {"attempted": 80, "ok": 80, "rpc_failed": 0, "mismatched": 0,
            "window_s": 2.0, "bytes_each": nbytes,
            "body_crc32": payload.bodies_crc32(seed, callers, nbytes - 8),
            "last_put": last}


def test_judge_passes_a_sound_report():
    got = kv_reference.judge(sound_report(), 2**31 + 3, 2, 4096)
    assert got == [("replies_wrong", 0, 0),
                   ("replies_missing_or_error", 0, 0),
                   ("digests_wrong", 0, 0)]
    # A caller that was never acknowledged has nothing to digest.
    quiet = sound_report()
    quiet["last_put"][1] = [0, 0, 0, 0]
    assert dict((n, v) for n, v, _ in kv_reference.judge(
        quiet, 2**31 + 3, 2, 4096))["digests_wrong"] == 0


@pytest.mark.parametrize("field, number", [
    ("mismatched", "replies_wrong"),
    ("rpc_failed", "replies_missing_or_error"),
    ("body_crc32", "digests_wrong"), ("word", "digests_wrong"),
    ("session", "digests_wrong")])
def test_judge_catches(field, number):
    report = sound_report()
    if field == "word":
        report["last_put"][1][3] ^= 1
    elif field == "session":
        report["last_put"][0][0] += 1 << 40  # not the seed's id
    else:
        report[field] += 1
    got = dict((n, v) for n, v, _ in kv_reference.judge(
        report, 2**31 + 3, 2, 4096))
    assert got[number] >= 1
    assert sum(got.values()) == got[number]


@pytest.mark.parametrize("acked, landed, chunks, executions, want", [
    (900, 900, 9, None, 0), (900, 1000, 10, None, 0),
    (900, 800, 8, None, 100), (900, None, None, None, 900),
    (900, 900, 9, 9, 0), (900, 900, 9, 7, 2), (900, 0, 0, 0, 900),
    (900, 900, 9, 12, 0)])
def test_landed_short(acked, landed, chunks, executions, want):
    assert kv_reference.landed_short(acked, landed, chunks,
                                     executions) == want


@pytest.mark.parametrize("readback, expected, evicted, want", [
    ({"readback_checked": 64, "readback_wrong": 0, "readback_failed": 0,
      "evicted_code": 2}, 64, 5, 0),
    ({"readback_checked": 64, "readback_wrong": 3, "readback_failed": 1,
      "evicted_code": 2}, 64, 5, 4),
    ({"readback_checked": 60, "readback_wrong": 0, "readback_failed": 0,
      "evicted_code": 2}, 64, 5, 4),           # checks the client left out
    ({"readback_checked": 64, "readback_wrong": 0, "readback_failed": 0,
      "evicted_code": 0}, 64, 5, 1),           # an evicted session answered
    ({"readback_checked": 8, "readback_wrong": 0, "readback_failed": 0,
      "evicted_code": 0}, 8, 0, 0)])           # nothing was evicted yet
def test_readback_wrong(readback, expected, evicted, want):
    assert kv_reference.readback_wrong(readback, expected, evicted) == want


def test_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    names = set()
    for mod in (kv_reference, kv_roofline):
        for node in ast.walk(ast.parse(inspect.getsource(mod))):
            if isinstance(node, ast.Import):
                names |= {a.name for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                names.add(node.module)
    assert names == {"numpy", "benchmark"}


def test_put_step_counts_one_read_and_one_write_of_the_chunk():
    assert kv_roofline.MODULE == "jit_kv_put_step"
    assert kv_roofline.kv_put_step_bytes(1 << 20) == 2 << 20
    assert kv_roofline.kv_put_step_least_s(
        1 << 20, "TPU v5 lite") == pytest.approx((2 << 20) / 819e9)
    with pytest.raises(KeyError):
        kv_roofline.kv_put_step_least_s(1 << 20, "TPU v9")


# ------------------------------------------------------------- the readers

def stage_dump(count, sum_us):
    return {"count": count, "sum_us": sum_us, "max_us": 9000,
            "buckets": [[52, count]]}


def served_obs(per_call_us, calls=100, warm=7, **vars_after):
    """Two scrapes `calls` calls apart; the warm-up's samples (before the
    first scrape) are ten times slower and must not show."""
    before = {s: stage_dump(warm, warm * 10 * us)
              for s, us in per_call_us.items()}
    after = {s: stage_dump(warm + calls, warm * 10 * us + calls * us)
             for s, us in per_call_us.items()}
    return {"before": {"status": {"stages": before}, "vars": {}},
            "after": {"status": {"stages": after}, "vars": vars_after}}


STAGE_US = {"tnet.consume_to_cut": 1800, "tfiber.dispatch_to_handler": 10,
            "trpc.handler": 38000, "trpc.respond": 5, "tnet.write_queue": 185,
            "tdev.take_wait": 26000, "tdev.reply": 50}


def test_stage_readers_read_the_window_exactly():
    obs = served_obs(STAGE_US)
    leg = manifest.reader("kv_device_leg_share").read
    assert leg(obs) == pytest.approx(100 * 38000 / 40000)
    assert manifest.reader("kv_take_wait_mean_us").read(
        obs) == pytest.approx(26000)
    for name, us in (("tnet_consume_to_cut_mean_us", 1800),
                     ("tnet_write_queue_mean_us", 185)):
        assert manifest.reader(name).read(obs) == pytest.approx(us)
    # A program without the stages, an empty window: None.
    assert leg(served_obs({"trpc.handler": 3000})) is None
    empty = served_obs(STAGE_US, calls=0)
    assert leg(empty) is None
    assert manifest.reader("kv_take_wait_mean_us").read(empty) is None


def test_pool_resident_share_is_the_gauges_ratio_at_the_windows_end():
    read = manifest.reader("kv_pool_resident_share").read
    obs = served_obs({}, rpc_kv_pool_bytes=4000.0,
                     rpc_kv_resident_bytes=3000.0)
    assert read(obs) == pytest.approx(75.0)
    assert read(served_obs({})) is None                # no such counters
    assert read(served_obs({}, rpc_kv_pool_bytes=0.0,
                           rpc_kv_resident_bytes=0.0)) is None


CHUNKS = 3  # of a hand-placed call


def place_puts(t0, calls, before=True):
    """Hand-placed spans of `calls` Puts of CHUNKS chunks each inside a
    window that starts at t0 (and of one call before it, which must not
    count): taker thread 1, dispatch thread 2, completion thread 3, and a
    join on no thread's line."""
    from brpc_tpu import spans

    launch = [("ring.acquire", 10), ("kv.fill", 250), ("ring.frame", 30),
              ("ring.h2d", 400)]
    starts = ([t0 - 1.0] if before else []) + [
        t0 + 0.01 + 0.02 * i for i in range(calls)]
    for n, base in enumerate(starts):
        spans._ring.append(("kv.take", base - 0.001, base, None, 1))
        at = base
        for k in range(CHUNKS):
            first = at
            for name, us in launch:
                spans._ring.append((name, at, at + us * 1e-6, n, 1))
                at += us * 1e-6
            spans._ring.append(("ring.launch", first, at + 5e-6, n, 1))
            at += 5e-6
            d = first + 0.001
            spans._ring.append(("ring.kernel_dispatch", d, d + 500e-6, n, 2))
            spans._ring.append(("ring.dispatch", d, d + 505e-6, n, 2))
            r = first + 0.002
            spans._ring.append(("ring.d2h_wait", r, r + 100e-6, n, 3))
            spans._ring.append(("ring.retire", r, r + 110e-6, n, 3))
        done = base + 0.006
        spans._ring.append(("kv.reply", done, done + 120e-6, n, 3))
        spans._ring.append(("kv.join", base, done, n, ("kv.join", base)))


def test_span_readers_take_time_a_chunk_and_a_call_inside_the_window():
    from brpc_tpu import spans

    readers = {n: manifest.reader(n).read for n in SPAN_READERS}
    spans.clear()
    t0 = time.monotonic()
    obs = {"t_first_op": t0, "window_s": 1.0}
    for read in readers.values():
        assert read({}) is None and read(obs) is None  # no span in there
    place_puts(t0, 20)
    assert readers["kv_fill_chunk_mean_us"](obs) == pytest.approx(250,
                                                                  rel=1e-3)
    assert readers["kv_h2d_dispatch_chunk_mean_us"](obs) == pytest.approx(
        400 + 500, rel=1e-3)
    assert readers["kv_reply_mean_us"](obs) == pytest.approx(120, rel=1e-3)
    # A join is a length, kept whole though retires and replies lie inside
    # it on the clock.
    assert readers["kv_join_mean_us"](obs) == pytest.approx(6000, rel=1e-3)
    assert kv_spans.mean_length_us(obs, "no.such.span") is None
    assert kv_spans.self_us_per(obs, ("kv.fill",), "no.such.span") is None
    # The accepted lane readers on the same spans: shares of the window
    # (1 s, 20 calls of 3 chunks).
    for name, us in (("ring_acquire_wait_share", 10),
                     ("ring_h2d_dispatch_share", 400 + 500)):
        assert manifest.reader(name).read(obs) == pytest.approx(
            100 * 20 * CHUNKS * us * 1e-6, rel=1e-3), name
    # Another path's spans alone (no kv.fill) are not a cache hand-off.
    spans.clear()
    spans._ring.append(("ring.launch", t0 + 0.1, t0 + 0.2, (1, 0), 1))
    spans._ring.append(("ring.h2d", t0 + 0.1, t0 + 0.2, (1, 0), 1))
    spans._ring.append(("tensor.fill", t0 + 0.1, t0 + 0.15, (1, 0), 1))
    for read in readers.values():
        assert read(obs) is None
    spans.clear()


TRACE_EVENTS = [
    ("/device:TPU:0", "XLA Modules", "jit_kv_put_step(7)", 0.10, 6e-6),
    ("/device:TPU:0", "XLA Ops", "dynamic-update-slice", 0.10, 3e-6),
    ("/device:TPU:0", "XLA Ops", "multiply_reduce_fusion", 0.100003, 2e-6),
    ("/device:TPU:0", "XLA Modules", "jit_kv_put_step(7)", 0.20, 6e-6),
    ("/device:TPU:0", "XLA Ops", "dynamic-update-slice", 0.20, 3e-6),
    ("/device:TPU:0", "XLA Ops", "multiply_reduce_fusion", 0.200003, 2e-6),
    ("/host:CPU", "python", "bench:window", 0.0, 1.0)]


def test_put_step_roofline_and_idle_share_from_a_hand_made_trace():
    from benchmark.drivers import kvcache as driver

    obs = {"trace": xplane.reduce_events(TRACE_EVENTS),
           "chunk_bytes": 1 << 20, "device_kind": "TPU v5 lite"}
    got = manifest.reader("kv_put_step_roofline").read(obs)
    assert got == pytest.approx(100 * ((2 << 20) / 819e9) / 5e-6)
    assert 0 < got < 100
    assert manifest.reader("device_idle_share.kv").read(obs) == \
        pytest.approx(100 * (1 - 10e-6))
    assert driver.step_executions(obs["trace"]) == 2
    assert driver.step_executions(None) is None
    assert driver.step_executions(xplane.reduce_events([])) is None


@pytest.mark.parametrize("events", [
    [],                                           # the CPU rehearsal's trace
    [("/device:TPU:0", "XLA Modules", "jit_tensor_step(1)", 0.1, 5e-6),
     ("/device:TPU:0", "XLA Ops", "fusion", 0.1, 4e-6)]],
    ids=["no_device_plane", "another_programs_module"])
def test_a_trace_without_the_step_reads_none_never_zero(events):
    obs = {"trace": xplane.reduce_events(events), "chunk_bytes": 1 << 20,
           "device_kind": "TPU v5 lite"}
    read = manifest.reader("kv_put_step_roofline").read
    assert read(obs) is None
    assert read(dict(obs, trace=None)) is None
    del obs["chunk_bytes"]  # an observation of another driver
    assert read(obs) is None


def test_span_notes_say_what_the_host_was_doing_and_give_the_columns():
    from benchmark.drivers import kvcache as driver
    from brpc_tpu import spans

    spans.clear()
    t0 = time.monotonic()
    place_puts(t0, 10, before=False)
    gaps, columns = driver.span_notes(t0, t0 + 1.0)
    assert all(k.startswith(xplane.SPAN_PREFIX) for k in gaps)
    assert gaps["bench:completion_thread_replies(kv.reply)"] == \
        pytest.approx(10 * 120e-6, rel=1e-3)
    assert gaps["bench:taker_h2d(ring.h2d)"] == pytest.approx(
        10 * CHUNKS * 400e-6, rel=1e-3)
    assert columns["chunks_in_the_spans_kept"] == 10 * CHUNKS
    assert columns["kv.fill"] == pytest.approx(250, abs=0.2)
    assert columns["ring.kernel_dispatch"] == pytest.approx(500, abs=0.2)
    spans.clear()
    assert driver.span_notes(t0, t0 + 1.0) == (
        {}, {"chunks_in_the_spans_kept": 0})


def test_window_delta_is_none_without_the_counter():
    from benchmark.drivers import kvcache as driver

    before, after = {"vars": {"a": 3.0}}, {"vars": {"a": 10.0, "b": 4.0}}
    assert driver.window_delta(before, after, "a") == 7.0
    assert driver.window_delta(before, after, "b") == 4.0
    assert driver.window_delta(before, after, "rpc_kv_chunks") is None


# ---------------------------------------------------------- the rehearsals

@pytest.fixture(scope="module")
def built(request):
    try:
        return request.getfixturevalue("cpp_build")
    except Exception as e:  # whatever the build raised: nothing to rehearse
        pytest.skip(f"libtpurpc.so cannot be built here: {e}")


KV_SIZES = ("--set", "bytes=65536", "--set", "layers=3",
            "--set", "chunk_bytes=16384")


def run_cell(cell, *extra, trace=0, seed=2**31 + 99):
    env = dict(os.environ)
    env.pop("BENCH_RUN", None)
    cmd = [sys.executable, str(REPO / "benchmark" / "run.py"), "--workload",
           cell, "--seed", str(seed), "--seconds", "1", "--trace",
           str(trace), "--rehearsal", "1", *extra]
    proc = subprocess.run(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, timeout=300)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    return proc, (json.loads(lines[-1]) if lines else None)


KV_COMPARED = ("replies_wrong", "replies_missing_or_error", "digests_wrong",
               "bytes_landed_short", "readback_wrong", "resident_wrong")


@pytest.mark.parametrize("trace", [0, 1])
def test_kv_rehearsal_comes_out_correct(built, trace):
    proc, line = run_cell(CELL, *KV_SIZES, trace=trace)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"      # never a device number
    assert line["not_the_committed_cell"]["rehearsal"] is True
    assert list(line)[-1] == "compared"
    assert {k: v["value"] for k, v in line["compared"].items()} == dict.fromkeys(
        KV_COMPARED, 0)
    info = json.loads(proc.stdout.splitlines()[0])
    notes = info["notes"]
    # The rehearsal runs through evictions and reads back after the drain.
    assert notes["evictions"] > 0 and notes["chunks_landed"] > 0
    assert notes["readback"]["readback_checked"] > 0
    assert notes["readback"]["evicted_code"] == kv_reference.NOT_FOUND
    assert notes["us_a_chunk"]["kv.fill"] > 0
    if trace:
        # Every reader that needs no device plane has a number.
        assert set(SPAN_READERS + STAGE_READERS + JOINED) | {
            "kv_pool_resident_share"} <= set(line["metrics"])
        assert "kv_put_step_roofline" not in line["metrics"]
        assert "device_idle_share.kv" not in line["metrics"]
        assert line["metrics"]["kv_device_leg_share"]["value"] > 50
        assert 0 < line["metrics"]["kv_pool_resident_share"]["value"] <= 100
        assert (line["metrics"]["kv_join_mean_us"]["value"]
                > line["metrics"]["kv_fill_chunk_mean_us"]["value"])
        gaps = dict(line["breakdown"]["idle_gaps"])
        assert any("kv.fill" in k for k in gaps)
        assert "setup_s" not in line["metrics"]
    else:
        assert {"goodput_gbps", "p99_us", "setup_s"} <= set(line["metrics"])
        assert line["metrics"]["goodput_gbps"]["value"] > 0
        assert line["metrics"]["p99_us"]["value"] > 0
    assert "correct: True" in proc.stderr.splitlines()[-1]


def test_a_single_chunk_call_rehearses_too(built):
    """At the program's own chunk size a 64 KiB layer is one chunk."""
    proc, line = run_cell(CELL, "--set", "bytes=65536", "--set", "layers=3")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is True and line["attempted"] > 0


def test_acknowledging_from_the_host_comes_out_not_correct(built):
    """Every word and every byte read back compares equal, and nothing
    landed on the device: the program's counter is not what the replier
    says of itself."""
    proc, line = run_cell(CELL, *KV_SIZES, "--control", "host_ack")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] > 0
    values = {k: v["value"] for k, v in line["compared"].items()}
    assert values.pop("bytes_landed_short") >= line["attempted"] * 65536
    assert set(values.values()) == {0}
    assert "correct: False" in proc.stderr.splitlines()[-1]


def test_writing_one_slot_on_comes_out_not_correct(built):
    """Every word is right (read from where the chunk was written), every
    byte landed, and the readback finds other bytes."""
    proc, line = run_cell(CELL, *KV_SIZES, "--control", "wrong_slot")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False and line["failed"] == 0
    values = {k: v["value"] for k, v in line["compared"].items()}
    assert values.pop("readback_wrong") > 0
    assert set(values.values()) == {0}
    assert "correct: False" in proc.stderr.splitlines()[-1]


def test_an_unknown_control_of_the_kv_cell_is_refused(built):
    proc, line = run_cell(CELL, *KV_SIZES, "--control", "flip_reply")
    assert proc.returncode != 0 and line is None
