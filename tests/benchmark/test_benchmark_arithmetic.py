"""The yardstick's arithmetic on fixed inputs: payloads from the seed,
percentiles and rates, the trace reduction, the rooflines' counts, the
references and what each check catches, the last line's schema.
Host-only: no chip, no build/, no jax."""
import json
import zlib

import numpy as np
import pytest

from benchmark import (manifest, payload, reference, roofline, stats,
                       xplane)
from benchmark import run as bench_run
from benchmark.drivers import ring, served

MAN = manifest.load()

# ---------------------------------------------------------------- payload


@pytest.mark.parametrize("seed", [0, 7, 12345678901, 2**31 + 12345])
def test_same_seed_same_bytes_other_seed_other_bytes(seed):
    a = payload.body(seed, 3, 4088)
    assert a == payload.body(seed, 3, 4088) and len(a) == 4088
    assert a != payload.body(seed + 1, 3, 4088)
    assert a != payload.body(seed, 4, 4088)
    w = payload.words(seed, 0, 1001)
    assert w.dtype == np.uint32 and w.size == 1001
    assert np.array_equal(w, payload.words(seed, 0, 1001))


def test_payload_matches_the_cpp_generators_digests():
    # Printed by build/echo_load --seed 12345678901 --callers 16 --bytes 4096
    # (client/echo_load.cc PayloadWord); the two generators must not drift.
    assert payload.bodies_crc32(12345678901, 16, 4096) == 3213575713
    assert payload.echo_reply_crc32(12345678901, 0, 7598, 4096) == 558201665
    assert payload.echo_reply_crc32(12345678901, 15, 7590, 4096) == 1976648798


def test_echo_tag_separates_callers_and_operations():
    tags = {payload.echo_tag(c, n) for c in range(4) for n in range(1, 50)}
    assert len(tags) == 4 * 49 and all(len(t) == 8 for t in tags)

# ------------------------------------------------------------------ stats


@pytest.mark.parametrize("q,want", [(0.5, 50), (0.99, 99), (0.999, 100),
                                    (1.0, 100), (0.01, 1)])
def test_percentile_is_nearest_rank_over_the_full_sample(q, want):
    sample = np.random.default_rng(1).permutation(np.arange(1, 101))
    assert stats.percentile(sample, q) == want


def test_percentile_refuses_an_empty_sample_and_a_bad_q():
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)
    with pytest.raises(ValueError):
        stats.percentile([1, 2], 0.0)


def test_rates_are_all_the_work_over_all_the_window():
    assert stats.rate(1000, 4.0) == 250.0
    assert stats.gbps(3e9, 2.0) == 1.5
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0.0
    assert stats.spread([9, 10, 10, 10, 10, 11]) == pytest.approx(0.05)


def closed_loop(latencies_s, callers: int, seconds: float):
    """Simulate the closed loop the served cells run: `callers` callers,
    each starting its next operation when the previous one returns, no
    operation started at or after `seconds`. `latencies_s(caller, n, t)`
    gives operation n's latency when started at time t. Returns
    (latencies, window_s): every started operation completes and is
    counted; the window ends at the last completion."""
    lat, last = [], 0.0
    for c in range(callers):
        t, n = 0.0, 0
        while t < seconds:
            d = latencies_s(c, n, t)
            lat.append(d)
            t += d
            n += 1
        last = max(last, t)
    return lat, last


def _loop(stall_at=None, stall_s=0.0):
    def latency(caller, n, t):
        hit = stall_at is not None and t <= stall_at < t + 0.001
        return 0.001 + (stall_s if hit else 0.0)
    return closed_loop(latency, callers=16, seconds=10.0)


def test_a_stall_moves_p99_and_qps_and_fails_nothing():
    calm, calm_window = _loop()
    stalled, window = _loop(stall_at=5.0, stall_s=1.5)
    # Closed loop: every operation started is completed, late or not.
    assert len(stalled) < len(calm)  # the stalled callers offered less
    assert stats.rate(len(stalled), window) < stats.rate(len(calm),
                                                         calm_window)
    assert stats.percentile(calm, 0.99) == pytest.approx(0.001)
    # 16 stalled operations among ~136k are beyond p99; a longer stall in
    # every caller's path shows in it.
    many, _ = closed_loop(
        lambda c, n, t: 0.001 + (0.02 if n % 50 == 0 else 0.0), 16, 10.0)
    assert stats.percentile(many, 0.99) == pytest.approx(0.021)
    assert max(stalled) == pytest.approx(1.501)


def test_a_stall_at_the_windows_end_is_drained_inside_the_window():
    lat, window = _loop(stall_at=9.9995, stall_s=2.0)
    assert window == pytest.approx(12.0, abs=0.01)
    # every operation started before the close is in the sample: 16 callers
    # x 1 ms for 10 s, the stalled ones included -- none dropped, failed 0
    assert 16 * 9_990 <= len(lat) <= 16 * 10_001

# ----------------------------------------------------------------- xplane

EVENTS = [
    ("/host:CPU", "python", "bench:window", 10.0, 1.0),
    ("/host:CPU", "python", "bench:launch", 10.0, 0.3),
    ("/host:CPU", "python", "bench:retire", 10.5, 0.5),
    ("/host:CPU", "python", "not_a_bench_span", 10.0, 1.0),
    ("/device:TPU:0", "XLA Ops", "fusion.1", 10.1, 0.1),
    ("/device:TPU:0", "XLA Ops", "fusion.1", 10.15, 0.1),   # overlaps
    ("/device:TPU:0", "XLA Ops", "all-gather.5", 10.6, 0.2),
    ("/device:TPU:0", "XLA Ops", "fusion.1", 9.0, 0.5),     # before window
    ("/device:TPU:0", "XLA Modules", "jit_step(8123456789)", 10.1, 0.25),
    ("/device:TPU:0", "XLA Modules", "jit_other", 10.6, 0.2),
    ("/device:TPU:0", "Steps", "0", 10.0, 1.0),
    ("/device:TPU:1", "XLA Ops", "fusion.1", 10.1, 0.1),
]


def test_interval_arithmetic():
    merged = xplane.merge([(3, 4), (1, 2), (1.5, 2.5), (5, 5)])
    assert merged == [(1, 2.5), (3, 4)]
    assert xplane.total(merged) == 2.5
    assert xplane.complement(merged, 0, 5) == [(0, 1), (2.5, 3), (4, 5)]
    assert xplane.overlap(merged, [(2, 3.5)]) == 1.0
    assert xplane.clip(merged, 2, 3.2) == [(2, 2.5), (3, 3.2)]


def test_trace_reduction_busy_union_idle_share_and_op_time():
    s = xplane.reduce_events(EVENTS)
    chip0 = s["chips"]["/device:TPU:0"]
    assert s["window_s"] == pytest.approx(1.0)
    assert chip0["busy_s"] == pytest.approx(0.15 + 0.2)     # union, clipped
    assert chip0["ops"]["fusion.1"] == [pytest.approx(0.2), 2]
    assert chip0["modules"]["jit_step"] == [pytest.approx(0.25), 1]
    assert s["busy_s"] == pytest.approx((0.35 + 0.1) / 2)   # mean over chips
    assert xplane.idle_share_pct(chip0["busy_s"], 1.0) == pytest.approx(65.0)
    # idle under each host span, on the busiest chip
    assert s["idle_gaps"]["bench:launch"] == pytest.approx(0.3 - 0.15)
    assert s["idle_gaps"]["bench:retire"] == pytest.approx(0.5 - 0.2)
    assert "not_a_bench_span" not in s["idle_gaps"]


def test_an_op_belongs_to_the_module_that_holds_its_start():
    s = xplane.reduce_events(EVENTS)
    assert s["chips"]["/device:TPU:0"]["module_ops"] == {
        "jit_step": {"fusion.1": [pytest.approx(0.2), 2]},
        "jit_other": {"all-gather.5": [pytest.approx(0.2), 1]}}
    got = xplane.module_ops(s, "jit_step")  # chip 1 has no module line
    assert got == {"": [pytest.approx(0.25), 1],
                   "fusion.1": [pytest.approx(0.2), 2]}
    assert xplane.module_ops(s, "jit_nowhere") == {}


def test_trace_reduction_takes_the_host_clocks_window_when_given():
    assert xplane.reduce_events(EVENTS, window_s=1.25)["window_s"] == 1.25
    empty = xplane.reduce_events([])
    assert empty["busy_s"] == 0.0 and empty["chips"] == {}


def test_device_ops_are_named_by_their_hlo_name():
    hlo = ("%multiply_reduce_fusion = u32[]{:T(128)} fusion(u32[261120]"
           "{0:T(1024)} %x.1), kind=kLoop, calls=%fused_computation")
    assert xplane.short_name(hlo) == "multiply_reduce_fusion"
    assert xplane.short_name("%all-gather.5 = u32[4]") == "all-gather.5"
    assert xplane.short_name("bench:window") == "bench:window"


def test_breakdown_has_at_most_ten_entries_a_list():
    events = [("/device:TPU:0", "XLA Ops", f"op{i}", float(i), 0.5)
              for i in range(15)]
    b = xplane.breakdown(xplane.reduce_events(events), {"why idle": 3.0})
    assert len(b["device_ops"]) == 10
    assert b["idle_gaps"] == [["why_idle", 3.0]]
    json.dumps(b)

# --------------------------------------------------------------- roofline


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("copied,want", [(False, 1044480), (True, 2088960)])
def test_touch_kernel_counts_one_read_and_a_write_only_if_copied(copied,
                                                                 want):
    assert roofline.touch_kernel_bytes(1044480, copied) == want
    least = roofline.touch_kernel_least_s(1044480, copied, "TPU v5 lite")
    assert least == pytest.approx(want / 819e9)


def test_a_roofline_share_is_never_clipped():
    assert roofline.share_pct(1.0, 8.0) == 12.5
    assert roofline.share_pct(2.0, 1.0) == 200.0
    with pytest.raises(ValueError):
        roofline.share_pct(1.0, 0.0)

# -------------------------------------------------------------- reference


def test_integrity_word_is_the_weighted_wraparound_sum():
    w = payload.words(5, 0, 3000)
    naive = 0
    for i, x in enumerate(w.tolist()):
        naive = (naive + x * (2 * i + 1)) & 0xFFFFFFFF
    assert reference.integrity_word(w) == naive
    swapped = w.copy()
    swapped[[0, 1]] = swapped[[1, 0]]
    assert reference.integrity_word(swapped) != naive


def _ring_answers():
    chunks = [payload.words(9, 0, 4096)[i * 1024:(i + 1) * 1024]
              for i in range(4)]
    returned = {k: c.copy() for k, c in enumerate(chunks)}
    words = [reference.integrity_word(c) for c in chunks] * 3
    return chunks, returned, words


def test_sound_ring_answers_compare_clean():
    chunks, returned, words = _ring_answers()
    assert reference.check_ring(chunks, returned, words, 12) == {
        "chunks_bytes_wrong": 0, "chunks_word_wrong": 0,
        "chunks_unanswered": 0}


@pytest.mark.parametrize("fault,number", [
    ("byte", "chunks_bytes_wrong"), ("word", "chunks_word_wrong"),
    ("lost_answer", "chunks_unanswered"), ("lost_word", "chunks_unanswered")])
def test_each_ring_fault_fails_its_number(fault, number):
    chunks, returned, words = _ring_answers()
    if fault == "byte":
        returned[2][7] += 1          # an answer altered where it is produced
    elif fault == "word":
        words[5] ^= 1
    elif fault == "lost_answer":
        del returned[3]
    else:
        words.pop()
    got = reference.check_ring(chunks, returned, words, 12)
    assert got[number] > 0


@pytest.mark.parametrize("calls", [3, 4, 9])
def test_ring_driver_keeps_each_chunks_newest_answer(calls):
    """The pipeline calls the integrity pass for chunk k of every pass as
    call k mod n: what is kept is the newest answer of each chunk."""
    kept = ring.KeepingAnswers(lambda x: (("y", x), ("chk", x)), 4)
    for i in range(calls):
        assert kept(i) == (("y", i), ("chk", i))   # passes through unchanged
    assert kept.calls == calls
    want = [None] * 4
    for i in range(calls):
        want[i % 4] = ("y", i)
    assert kept.last == want


@pytest.mark.parametrize("payload_bytes,chunk_kb,n,chunk_bytes", [
    (67108864, 1020, 64, 1044480), (2097152, 256, 8, 262144),
    (1000, 1020, 1, 1044480)])
def test_ring_chunks_are_cut_as_the_program_cuts_them(payload_bytes,
                                                      chunk_kb, n,
                                                      chunk_bytes):
    chunks = ring.make_chunks(3, payload_bytes, chunk_kb)
    assert len(chunks) == n and chunks[0].nbytes == chunk_bytes
    assert np.array_equal(chunks[-1], ring.make_chunks(
        3, payload_bytes, chunk_kb)[-1])           # the same seed, the same
    whole = payload.words(3, 0, n * chunk_bytes // 4)
    assert np.array_equal(np.concatenate(chunks), whole)


# ----------------------------------------------------------- served driver


def _report(seed=77, callers=3, nbytes=64, ok=300):
    seqs = [101, 102, 103]
    return {"attempted": ok, "ok": ok, "rpc_failed": 0, "mismatched": 0,
            "window_s": 2.0, "client_cpu_s": 0.5, "bytes_each": nbytes,
            "body_crc32": payload.bodies_crc32(seed, callers, nbytes),
            "last_seq": seqs,
            "last_reply_crc32": [payload.echo_reply_crc32(seed, c, s, nbytes)
                                 for c, s in enumerate(seqs)],
            "errors": {}, "workers": 9, "per_s": [140, 160, 3]}


def test_served_judge_passes_a_sound_report():
    assert all(v == 0 for _, v, _ in served.judge(_report(), 77, 3, 64))


@pytest.mark.parametrize("field,number", [
    ("mismatched", "replies_wrong"),
    ("rpc_failed", "replies_missing_or_error"),
    ("body_crc32", "digests_wrong"), ("last_reply_crc32", "digests_wrong"),
    ("last_seq", "digests_wrong")])
def test_served_judge_catches(field, number):
    rep = _report()
    if isinstance(rep[field], list):
        rep[field][1] += 1
    else:
        rep[field] += 1
    got = {n: (v, lim) for n, v, lim in served.judge(rep, 77, 3, 64)}
    assert got[number][0] > got[number][1] == 0


def test_served_metrics_count_verified_work_over_the_whole_window():
    rep = _report(ok=300)
    rep["attempted"], rep["mismatched"] = 310, 10
    lat = np.arange(1, 311, dtype=np.uint64) * 1000  # 1..310 us
    e2e, notes = served.metrics(rep, lat)
    assert e2e["qps"] == 150.0                       # verified only
    assert e2e["goodput_gbps"] == 300 * 64 / 2.0 / 1e9
    assert e2e["p99_us"] == 307.0 and notes["p50_us"] == 155.0
    assert notes["samples"] == 310                   # the tail of ALL requests


LOOPS_TEXT = """event dispatchers (epoll loops)
loop  cpu   epoll_waits   events      wakeups  batch  ev/wake p50/p99   wake->dispatch us p50/p99/max
0     -1    1200          3400        900      64     1/3          9/41/380
1     -1    10            10          2        64     1/1          992/992/1016

run-to-completion dispatch
inline_dispatches: 50  inline_overflows: 0  inline_handlers: 40  coalesced_writes: 1234

fiber scheduler pools
pool  workers  live_fibers  steals      remote_overflows  urgent_handoffs  runq_highwater
0     9        1            17          0                 0                24
"""


def test_portal_pages_parse():
    loops = served.parse_loops(LOOPS_TEXT)
    assert loops["loops"][0]["wake_to_dispatch_us"] == {
        "p50": 9, "p99": 41, "max": 380}
    assert loops["loops"][1]["events"] == 10
    assert loops["counters"]["coalesced_writes"] == 1234
    assert loops["pools"] == [{"pool": 0, "workers": 9, "steals": 17,
                               "runq_highwater": 24}]
    v = served.parse_vars("rpc_socket_coalesced_writes : 2\n"
                          "x_series : 2 series\nrpc_f : 1.5  ▁▁█\n")
    assert v == {"rpc_socket_coalesced_writes": 2.0, "x_series": 2.0,
                 "rpc_f": 1.5}

# ---------------------------------------------------- readers on fixed obs

_SCRAPE0 = {"vars": {}, "status": {"methods": {"benchpb.EchoService.Echo": {
    "count": 1000}}},
    "loops": {"loops": [], "pools": [],
              "counters": {"coalesced_writes": 100}}}
_SCRAPE1 = {"vars": {"rpc_pool_descriptor_send_bytes": 2.5e8},
            "status": {"methods": {"benchpb.EchoService.Echo": {
                "count": 11000}}},
            "loops": {"loops": [], "pools": [],
                      "counters": {"coalesced_writes": 4100}}}
_TRACE = xplane.reduce_events([
    ("/host:CPU", "py", "bench:window", 0.0, 1.0),
    ("/device:TPU:0", "XLA Ops", "multiply_reduce_fusion", 0.1, 4e-6),
    ("/device:TPU:0", "XLA Ops", "multiply_reduce_fusion", 0.2, 4e-6),
    ("/device:TPU:0", "XLA Ops", "copy.3", 0.3, 0.5),  # not the pass's own
    ("/device:TPU:0", "XLA Modules", "jit_touch(42)", 0.1, 5e-6),
    ("/device:TPU:0", "XLA Modules", "jit_touch(42)", 0.2, 5e-6),
    ("/device:TPU:0", "XLA Modules", "jit_sum(7)", 0.3, 0.5)])
OBS = {"ops": 10000, "payload_bytes": 1e9, "client_cpu_s": 0.2,
       "server_cpu_s": 0.3, "before": _SCRAPE0, "after": _SCRAPE1,
       "end_to_end": {"goodput_gbps": 0.7}, "raw_link_gbps": 1.4,
       "trace": _TRACE, "chunk_bytes": 1044480,
       "device_kind": "TPU v5 lite"}


@pytest.mark.parametrize("name,want", [
    ("client_cpu_us_per_op", 20.0), ("server_cpu_us_per_op", 30.0),
    ("server_cpu_us_per_mb", 300.0),
    ("tnet_coalesced_write_share", 40.0), ("tici_desc_share", 25.0),
    ("ring_vs_raw_ratio", 0.5),
    ("touch_kernel_roofline", 100 * (1044480 / 819e9) / 4e-6),
    ("device_idle_share.ring", 100 * (1 - (8e-6 + 0.5)))])
def test_reader_reads_its_number_from_a_fixed_observation(name, want):
    assert manifest.reader(name).read(OBS) == pytest.approx(want)


def test_the_integrity_pass_copied_out_counts_a_write_too():
    events = [("/device:TPU:0", "XLA Modules", "jit_touch(1)", 0.1, 9e-6),
              ("/device:TPU:0", "XLA Ops", "multiply_reduce_fusion", 0.1,
               4e-6),
              ("/device:TPU:0", "XLA Ops", "copy.1", 0.100005, 4e-6)]
    obs = dict(OBS, trace=xplane.reduce_events(events))
    assert manifest.reader("touch_kernel_roofline").read(obs) == \
        pytest.approx(100 * (2 * 1044480 / 819e9) / 8e-6)


@pytest.mark.parametrize("events,outcome", [
    ([], None),                                   # the CPU rehearsal's trace
    ([("/device:TPU:0", "XLA Modules", "jit_renamed(1)", 0.1, 5e-6),
      ("/device:TPU:0", "XLA Ops", "fusion", 0.1, 4e-6)], LookupError)])
def test_a_trace_without_the_integrity_pass_is_silent_only_off_the_chip(
        events, outcome):
    obs = dict(OBS, trace=xplane.reduce_events(events))
    reader = manifest.reader("touch_kernel_roofline")
    if outcome is None:
        assert reader.read(obs) is None
        assert reader.read(dict(OBS, trace=None)) is None
    else:
        with pytest.raises(outcome, match="jit_renamed"):
            reader.read(obs)

# ------------------------------------------------------------ the last line


def _line(trace, failed=0, wrong=0):
    cell = manifest.cell(MAN, "bulk_64m_ring")
    obs = dict(OBS, attempted=640, failed=failed,
               checks=[("chunks_bytes_wrong", wrong, 0),
                       ("chunks_word_wrong", 0, 0)])
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 1}
    return bench_run.result_line(MAN, cell, obs, trace, 9.5, dev)


def test_last_line_has_the_contracts_keys_and_the_cells_metrics():
    line = _line(trace=False)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert {"goodput_gbps", "setup_s"} <= set(line["metrics"])
    assert line["metrics"]["goodput_gbps"] == {"value": 0.7, "unit": "GB/s"}
    assert line["correct"] is True and "breakdown" not in line
    traced = _line(trace=True)
    # what this observation gives a reader something to read for; the
    # span readers find no window in it and are left out of the line
    assert {"ring_vs_raw_ratio", "touch_kernel_roofline",
            "device_idle_share.ring"} <= set(traced["metrics"])
    assert "ring_launcher_rest_share" not in traced["metrics"]
    assert len(traced["breakdown"]["device_ops"]) <= 10
    json.dumps(traced)


@pytest.mark.parametrize("failed,wrong", [(1, 0), (0, 1)])
def test_a_failed_operation_or_a_number_over_its_limit_is_not_correct(
        failed, wrong):
    assert _line(trace=False, failed=failed, wrong=wrong)["correct"] is False


def test_a_metric_the_driver_did_not_give_is_an_error_not_a_gap():
    cell = manifest.cell(MAN, "echo_4k_c16")
    obs = dict(OBS, attempted=1, failed=0, checks=[])
    with pytest.raises(manifest.ManifestError):
        bench_run.result_line(MAN, cell, obs, False, 1.0, {})


def test_zlib_digest_is_the_one_the_client_uses():
    assert zlib.crc32(b"123456789") == 0xCBF43926
