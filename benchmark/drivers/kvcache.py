"""Driver `kvcache`: kvpb.Cache served from THIS process, which holds the
chip (`brpc_tpu.kv_service.serve` on `run.devices[0]`: a `Server` whose
handlers park each Put for a taker thread that cuts it into the staging
lane's chunks -- slot, in-place frame, H2D, the jitted put step that writes
the chunk into a pool that stays in HBM -- and a completion thread that
answers when the last chunk's word is back), under one host-only client
process of the benchmark's own (client/kv_load.cc), a closed loop of
prefill streams over the shm link. The window is bracketed by scrapes of the
program's own portal (same port), as `tensor.py` brackets its own.

`goodput_gbps` counts request-payload bytes whose Put was acknowledged with
the right word, once each, over the window. `p99_us` is the callers' own,
over every acknowledged Put of the window. After the window's last scrape,
in no timed number, the client reads layers back (`readback_wrong`) and the
service's table is held to the reference's replay of the admissions
(`resident_wrong`).

Controls (never run by the benchmark's own runs). `host_ack`: in the
program's place, a handler of the benchmark's own behind the program's pull
server that keeps every layer in host memory and answers with the right
word, computed on the host: every reply and every readback compares equal,
and still no byte landed on the device, so `bytes_landed_short` is every
byte. `wrong_slot`: the program's service with its put step handed a place
one slot on: every word is right (it is read from where the chunk was
written) and the readback finds other bytes. `correct` must come out false
in both.
"""
import json
import os
import subprocess
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from benchmark import device, kv_reference, kv_roofline, tracing, xplane
from benchmark.drivers.served import (Children, http_get, metrics,
                                      read_line, scrape)
from brpc_tpu import kv_service, native

ROOT = Path(__file__).resolve().parent.parent.parent
CLIENT_SRC = ROOT / "benchmark" / "client" / "kv_load.cc"
TRACER_SETTLE_S = 0.5  # traced runs: the device tracer attaches after start
READBACK_SESSIONS, READBACK_LAYERS = 2, 8  # kv_load.cc's, a caller

# What the host was doing while the chip idled: the program's spans
# (brpc_tpu/spans.py) by thread, as notes beside the trace's idle gaps.
GAP_SPANS = {
    "bench:taker_waits_for_a_call(kv.take)": ("kv.take",),
    "bench:taker_copies_chunk_into_slot_and_frames(kv.fill+ring.frame)":
        ("kv.fill", "ring.frame"),
    "bench:taker_h2d(ring.h2d)": ("ring.h2d",),
    "bench:dispatch_thread_steps_a_chunk(ring.kernel_dispatch)":
        ("ring.kernel_dispatch",),
    "bench:taker_waits_for_credit_or_slot(ring.acquire)": ("ring.acquire",),
    "bench:completion_thread_waits_for_the_word(ring.d2h_wait)":
        ("ring.d2h_wait",),
    "bench:completion_thread_replies(kv.reply)": ("kv.reply",),
}
# The three threads' columns, microseconds a chunk, as PERF.md section 5
# gives them for the other lane cells: notes, not metrics.
COLUMNS = ("kv.take", "ring.acquire", "kv.fill", "ring.frame", "ring.h2d",
           "ring.launch", "ring.kernel_dispatch", "ring.dispatch",
           "ring.d2h_wait", "ring.complete", "ring.retire", "kv.reply",
           "kv.evict")


def build_client(build_dir: Path) -> Path:
    """The load generator, compiled against this checkout's build (the
    stub's code is in libtpurpc.so); reused while it is newer than its
    source and the library."""
    out = build_dir / "kv_load"
    lib = build_dir / "libtpurpc.so"
    if out.exists() and out.stat().st_mtime >= max(
            CLIENT_SRC.stat().st_mtime, lib.stat().st_mtime):
        return out
    tmp = build_dir / f"kv_load.{os.getpid()}.tmp"
    cmd = ["g++", "-std=c++17", "-O2", "-fno-omit-frame-pointer",
           f"-I{ROOT / 'cpp'}", f"-I{build_dir}", str(CLIENT_SRC), "-o",
           str(tmp), f"-L{build_dir}", "-ltpurpc",
           f"-Wl,-rpath,{build_dir}", "-lprotobuf", "-lpthread", "-lz"]
    proc = subprocess.run(cmd, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"{' '.join(cmd)} failed:\n{proc.stdout[-3000:]}")
    os.replace(tmp, out)  # atomic: two runs may build at once
    return out


class HostAcks:
    """Control `host_ack`: the program's pull server with the benchmark's
    own handler behind it -- the reference's table in host memory, no lane,
    no device."""

    def __init__(self, slots: int):
        self.slots = slots
        self.table = {}  # session -> {layer: bytes}, oldest first
        self.admitted = {}
        self.server = native.PullServer()
        self.port = self.server.port
        self._thread = threading.Thread(target=self._answer_from_the_host)
        self._thread.start()

    def _answer_from_the_host(self):
        try:
            while True:
                call = self.server.take(50_000)
                if call is None:
                    continue
                if call.method == native.PUT:
                    x = np.empty(call.nbytes, dtype=np.uint8)
                    call.copy_into(x)
                    if call.session not in self.table:
                        if len(self.table) == self.slots:
                            del self.table[next(iter(self.table))]
                        self.admitted[call.session] = len(self.admitted)
                        self.table[call.session] = {}
                    self.table[call.session][call.layer] = x
                    call.reply_put(kv_reference.word(x.tobytes()),
                                   self.admitted[call.session])
                    continue
                kept = self.table.get(call.session, {}).get(call.layer)
                if kept is None:
                    call.fail(kv_reference.NOT_FOUND, "not in the table")
                else:
                    call.reply(kept)
        except native.ServerClosedError:
            pass

    def close(self):
        self.server.close_queue()
        self._thread.join()
        self.server.stop()


def one_slot_on(service):
    """Control `wrong_slot`: every chunk is written one slot's length
    further into the layer's buffer than the table says."""
    import jax.numpy as jnp

    real = service._put_step
    shift = jnp.array([service.row_chunks * service.chunk_bytes // 4, 0],
                      jnp.int32)
    service._put_step = lambda pool, x, where: real(pool, x, where + shift)


def span_notes(t0: float, t1: float) -> tuple:
    """(gap notes: seconds of the window each host activity took; columns:
    self time a chunk of each of the lane's spans, microseconds), both from
    the program's spans."""
    try:
        from brpc_tpu import spans
    except ImportError:
        return {}, {}
    records = spans.snapshot(t0, t1)
    own = spans.self_times(records)
    notes = {note: sum(own.get(n, 0.0) for n in names)
             for note, names in GAP_SPANS.items()}
    chunks = sum(1 for rec in records if rec[0] == "ring.launch")
    columns = {n: round(1e6 * own[n] / chunks, 1)
               for n in COLUMNS if n in own and chunks}
    columns["chunks_in_the_spans_kept"] = chunks
    return {k: v for k, v in notes.items() if v > 0}, columns


def step_executions(summary):
    """Executions of the put step's module in a trace with device planes;
    None where there is no such trace (untraced run, the CPU rehearsal)."""
    if not summary or not summary["chips"]:
        return None
    return xplane.module_ops(summary, kv_roofline.MODULE).get(
        "", [0.0, 0])[1]


def window_delta(before: dict, after: dict, name: str):
    """after - before of one of the program's counters; None where the
    program has none of that name."""
    if name not in after["vars"]:
        return None
    return after["vars"][name] - before["vars"].get(name, 0)


def run(run) -> dict:
    cfg, tr = run.config, run.traffic
    callers, nbytes = int(tr["callers"]), int(tr["bytes"])
    layers, slots = int(tr["layers"]), int(cfg["pool_sessions"])
    if tr["loop"] != "closed" or nbytes % 8 or nbytes < 24:
        raise ValueError("the kvcache driver runs closed loops of layers "
                         "that are a multiple of 8 bytes, 24 at least")
    # The program's own default unless a sweep or the rehearsal says.
    chunk_bytes = int(tr.get("chunk_bytes", kv_service.CHUNK_BYTES))
    window = tracing.window_seconds(run.seconds, run.trace)
    build_dir = native.build()
    client_bin = build_client(build_dir)
    run.mark("built")
    # The configuration's socket buffers, as brpc_echo_shm's server sets its
    # own: the embedding process's to choose, before it serves.
    for flag in ("socket_send_buffer_size", "socket_recv_buffer_size"):
        native.set_flag(flag, cfg["socket_buffer_bytes"])
    if run.control == "host_ack":
        service = HostAcks(slots)
    elif run.control in (None, "wrong_slot"):
        service = kv_service.serve(
            run.devices[0], layers=layers, sessions=slots,
            layer_bytes=nbytes, depth=int(cfg["ring_depth"]),
            chunk_bytes=chunk_bytes)
        if run.control == "wrong_slot":
            one_slot_on(service)
    else:
        raise ValueError(f"kvcache: unknown control {run.control!r}")
    port = service.port
    run.mark("serving")

    fd, sample_path = tempfile.mkstemp(prefix="bench-lat-", suffix=".bin")
    os.close(fd)
    try:
        with Children() as kids:
            client = kids.spawn(
                [client_bin, "--port", port, "--callers", callers,
                 "--bytes", nbytes, "--layers", layers, "--seed", run.seed,
                 "--seconds", window, "--warm-ms", tr["warm_ms"],
                 "--timeout-ms", cfg["timeout_ms"], "--sample-out",
                 sample_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE)
            if read_line(client, 120, "client READY") != "READY":
                raise RuntimeError("client did not say READY")
            run.mark("client_ready")
            deadline_s = cfg["timeout_ms"] / 1e3 + 30
            own0 = os.times()
            with tracing.TraceWindow(run.trace) as tw:
                if run.trace:
                    time.sleep(TRACER_SETTLE_S)
                before = scrape(port)
                http_get(port, "/loops?reset=1")
                t_first = time.monotonic()
                client.stdin.write(b"GO\n")
                client.stdin.flush()
                with tw.span("harness_waits_for_the_clients_report"):
                    report = json.loads(read_line(
                        client, window + deadline_s, "client result"))
                t_last = time.monotonic()
                after = scrape(port)
            own1 = os.times()
            peak = device.memory_peak_bytes(run.devices)
            # Outside every timed number: what the table must hold, then
            # the client's reads of it.
            resident, evicted, problems = kv_reference.resident_after(
                [(s, a) for s, a, _ in report["sessions"]], slots)
            held = set(service.table)
            probe = evicted[0] if evicted else 0
            client.stdin.write(f"READBACK {probe}\n".encode())
            client.stdin.flush()
            readback = json.loads(read_line(
                client, deadline_s + 120, "client readback"))
        lat_ns = np.fromfile(sample_path, dtype="<u8")
    finally:
        os.unlink(sample_path)
        service.close()
    failure = getattr(service, "failure", None)
    if failure is not None:
        raise RuntimeError(f"the service shut itself down: {failure!r}")

    e2e, notes = metrics(report, lat_ns)
    summary = tw.summary()
    executions = step_executions(summary)
    completed = {}  # caller -> sessions it put every layer of
    for session, _, acked in report["sessions"]:
        caller = (session >> 32) & 0xFF
        completed[caller] = completed.get(caller, 0) + (acked == layers)
    expected_checks = sum(min(n, READBACK_SESSIONS) for n in
                          completed.values()) * min(READBACK_LAYERS, layers)
    checks = kv_reference.judge(report, run.seed, callers, nbytes)
    checks += [
        ("bytes_landed_short", kv_reference.landed_short(
            report["ok"] * nbytes,
            window_delta(before, after, "rpc_kv_bytes_landed"),
            window_delta(before, after, "rpc_kv_chunks"), executions), 0),
        ("readback_wrong", kv_reference.readback_wrong(
            readback, expected_checks, probe), 0),
        ("resident_wrong", len(resident ^ held) + problems
         + report["admitted_moved"], 0)]
    gaps, columns = span_notes(t_first, t_last)
    run.notes.update(
        notes, client_errors=report["errors"],
        client_workers=report["workers"], calls_per_s=e2e["qps"],
        chunk_bytes=chunk_bytes, step_executions_traced=executions,
        chunks_landed=window_delta(before, after, "rpc_kv_chunks"),
        evictions=window_delta(before, after, "rpc_kv_evictions"),
        sessions_admitted=len(report["sessions"]), readback=readback,
        us_a_chunk=columns,
        process_cpu_cores=(sum(own1[:2]) - sum(own0[:2])) / (t_last - t_first),
        ops_per_s=report["per_s"][:int(report["window_s"])])
    return {
        "attempted": report["attempted"],
        "failed": report["rpc_failed"] + report["mismatched"],
        "window_s": report["window_s"], "t_first_op": t_first,
        "memory_peak_bytes": peak,
        "end_to_end": {"goodput_gbps": e2e["goodput_gbps"],
                       "p99_us": e2e.get("p99_us")},
        "checks": checks, "trace": summary, "gap_notes": gaps,
        "ops": report["ok"], "payload_bytes": report["ok"] * nbytes,
        "bytes_each": nbytes, "chunk_bytes": chunk_bytes,
        "device_kind": run.devices[0].device_kind,
        "client_cpu_s": report["client_cpu_s"],
        "before": before, "after": after,
        # The client process's own stage table and counters at the window's
        # two edges (served.py hands on echo_load.cc's the same way).
        "client_before": report.get("client_before"),
        "client_after": report.get("client_after"),
    }
