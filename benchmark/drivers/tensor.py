"""Driver `tensor`: tensor.Step served from THIS process, which holds the
chip (`brpc_tpu.tensor_service.serve` on `run.devices[0]`: a `Server` whose
handler parks each call for a taker thread that submits it to the staging
lane -- slot, in-place frame, H2D, the jitted step, D2H -- and a completion
thread that replies), under one host-only client process of the benchmark's
own (client/tensor_load.cc), a closed loop over the shm link. The payload
crosses the chip inside the RPC. The window is bracketed by scrapes of the
program's own portal (same port), as `served.py` brackets its server's.

`goodput_gbps` counts request-payload bytes whose reply came back verified,
once each, over the window; the reply's 4-byte word is not counted. `p99_us`
is the callers' own, over every verified call of the window, as
perf_analyzer (the configuration's source) reports it.

Controls (never run by the benchmark's own runs): in the program's place, a
handler of the benchmark's own behind the program's pull server that answers
every call from the host, without the lane. `host_echo` gives the request's
bytes and the reference's word: the step is not the identity, so every reply
compares wrong. `host_step` gives the RIGHT answer, computed on the host:
every reply compares equal, and still no step came back from the device, so
`device_calls_short` is every call. `correct` must come out false in both.
"""
import json
import os
import subprocess
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from benchmark import device, reference, stats, tensor_reference, tracing
from benchmark import tensor_roofline, xplane
from benchmark.drivers.served import (Children, http_get, metrics,
                                      read_line, scrape)
from brpc_tpu import native, tensor_service

ROOT = Path(__file__).resolve().parent.parent.parent
CLIENT_SRC = ROOT / "benchmark" / "client" / "tensor_load.cc"
TRACER_SETTLE_S = 0.5  # traced runs: the device tracer attaches after start

# What the host was doing while the chip idled: the program's spans
# (brpc_tpu/spans.py) by thread, as notes beside the trace's idle gaps.
GAP_SPANS = {
    "bench:taker_waits_for_a_call(tensor.take)": ("tensor.take",),
    "bench:taker_copies_request_into_slot_and_frames(tensor.fill+ring.frame)":
        ("tensor.fill", "ring.frame"),
    "bench:taker_h2d_and_dispatch(ring.h2d+ring.kernel_dispatch)":
        ("ring.h2d", "ring.kernel_dispatch"),
    "bench:taker_waits_for_credit_or_slot(ring.acquire)": ("ring.acquire",),
    "bench:completion_thread_waits_for_d2h(ring.d2h_wait)":
        ("ring.d2h_wait",),
    "bench:completion_thread_replies(tensor.reply)": ("tensor.reply",),
}


def build_client(build_dir: Path) -> Path:
    """The load generator, compiled against this checkout's build (the
    stub's code is in libtpurpc.so); reused while it is newer than its
    source and the library."""
    out = build_dir / "tensor_load"
    lib = build_dir / "libtpurpc.so"
    if out.exists() and out.stat().st_mtime >= max(
            CLIENT_SRC.stat().st_mtime, lib.stat().st_mtime):
        return out
    tmp = build_dir / f"tensor_load.{os.getpid()}.tmp"
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


class HostAnswers:
    """Controls `host_echo` and `host_step`: the program's pull server with
    the benchmark's own broken handler behind it -- no lane, no device."""

    def __init__(self, key=None):
        self.key = key  # None: echo the request; else the step, on the host
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
                x = np.empty(call.nbytes, dtype=np.uint8)
                call.copy_into(x)
                if self.key is None:
                    word = reference.integrity_word(x.view("<u4"))
                    answer = x.tobytes() + word.to_bytes(4, "little")
                else:
                    answer = tensor_reference.step(x.tobytes(), self.key)
                call.reply(np.frombuffer(answer, dtype=np.uint8))
        except native.ServerClosedError:
            pass

    def close(self):
        self.server.close_queue()
        self._thread.join()
        self.server.stop()


def gap_notes(t0: float, t1: float) -> dict:
    """Seconds of the window each host activity took, from the program's
    spans (self times, per thread)."""
    try:
        from brpc_tpu import spans
    except ImportError:
        return {}
    own = spans.self_times(spans.snapshot(t0, t1))
    notes = {note: sum(own.get(n, 0.0) for n in names)
             for note, names in GAP_SPANS.items()}
    return {k: v for k, v in notes.items() if v > 0}


STOP_US = 50_000  # what a call's five milliseconds do not explain


def stops(after: dict, t0: float, t1: float) -> dict:
    """Where a stop of the callers sat, if there was one: the program's
    stages whose maximum since start is over STOP_US, and the three longest
    leaf spans of the window's end (what each of the two threads was in);
    notes, not metrics."""
    slow = {name: st["max_us"] for name, st
            in after.get("status", {}).get("stages", {}).items()
            if st.get("max_us", 0) >= STOP_US}
    try:
        from brpc_tpu import spans
    except ImportError:
        return {"stage_max_us": slow}
    leaves = sorted(((end - start, name) for name, start, end, *_
                     in spans.snapshot(t0, t1)
                     if name not in ("ring.launch", "ring.retire")),
                    reverse=True)[:3]
    return {"stage_max_us": slow,
            "longest_spans_us": [[name, round(1e6 * s)] for s, name in leaves]}


def step_executions(summary):
    """Executions of the step's module in a trace with device planes; None
    where there is no such trace (untraced run, the CPU rehearsal)."""
    if not summary or not summary["chips"]:
        return None
    return xplane.module_ops(summary, tensor_roofline.MODULE).get(
        "", [0.0, 0])[1]


def run(run) -> dict:
    cfg, tr = run.config, run.traffic
    callers, nbytes = int(tr["callers"]), int(tr["bytes"])
    if tr["loop"] != "closed" or nbytes % 8 or nbytes < 16:
        raise ValueError("the tensor driver runs closed loops of payloads "
                         "that are a multiple of 8 bytes, 16 at least")
    key = int(cfg["key"])
    window = tracing.window_seconds(run.seconds, run.trace)
    build_dir = native.build()
    client_bin = build_client(build_dir)
    run.mark("built")
    # The configuration's socket buffers, as brpc_echo_shm's server sets its
    # own: the embedding process's to choose, before it serves.
    for flag in ("socket_send_buffer_size", "socket_recv_buffer_size"):
        native.set_flag(flag, cfg["socket_buffer_bytes"])
    if run.control in ("host_echo", "host_step"):
        service = HostAnswers(key if run.control == "host_step" else None)
    elif run.control is None:
        service = tensor_service.serve(run.devices[0],
                                       depth=int(cfg["ring_depth"]),
                                       max_bytes=nbytes, key=key)
    else:
        raise ValueError(f"tensor: unknown control {run.control!r}")
    port = service.port
    run.mark("serving")

    fd, sample_path = tempfile.mkstemp(prefix="bench-lat-", suffix=".bin")
    os.close(fd)
    try:
        with Children() as kids:
            client = kids.spawn(
                [client_bin, "--port", port, "--callers", callers,
                 "--bytes", nbytes, "--seed", run.seed, "--key", key,
                 "--seconds", window, "--warm-ms", tr["warm_ms"],
                 "--timeout-ms", cfg["timeout_ms"], "--sample-out",
                 sample_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE)
            if read_line(client, 120, "client READY") != "READY":
                raise RuntimeError("client did not say READY")
            run.mark("client_ready")
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
                        client, window + cfg["timeout_ms"] / 1e3 + 30,
                        "client result"))
                t_last = time.monotonic()
                after = scrape(port)
            own1 = os.times()
        lat_ns = np.fromfile(sample_path, dtype="<u8")
        peak = device.memory_peak_bytes(run.devices)
    finally:
        os.unlink(sample_path)
        service.close()
    failure = getattr(service, "failure", None)
    if failure is not None:
        raise RuntimeError(f"the service shut itself down: {failure!r}")

    e2e, notes = metrics(report, lat_ns)
    summary = tw.summary()
    through_lane = (after["vars"].get("rpc_tensor_calls", 0)
                    - before["vars"].get("rpc_tensor_calls", 0))
    executions = step_executions(summary)
    checks = tensor_reference.judge(report, run.seed, callers, nbytes, key)
    answered = report["attempted"] - report["rpc_failed"]
    checks.append(("device_calls_short", tensor_reference.device_calls_short(
        answered, through_lane, executions), 0))
    run.notes.update(
        notes, client_errors=report["errors"],
        client_workers=report["workers"], calls_per_s=e2e["qps"],
        calls_through_lane=through_lane, step_executions_traced=executions,
        process_cpu_cores=(sum(own1[:2]) - sum(own0[:2])) / (t_last - t_first),
        ops_per_s=report["per_s"][:int(report["window_s"])],
        **stops(after, t_first, t_last))
    return {
        "attempted": report["attempted"],
        "failed": report["rpc_failed"] + report["mismatched"],
        "window_s": report["window_s"], "t_first_op": t_first,
        "memory_peak_bytes": peak,
        "end_to_end": {"goodput_gbps": e2e["goodput_gbps"],
                       "p99_us": e2e.get("p99_us")},
        "checks": checks, "trace": summary,
        "gap_notes": gap_notes(t_first, t_last),
        "ops": report["ok"], "payload_bytes": report["ok"] * nbytes,
        "bytes_each": nbytes, "device_kind": run.devices[0].device_kind,
        "client_cpu_s": report["client_cpu_s"],
        "before": before, "after": after,
        # The client process's own stage table and counters at the window's
        # two edges (served.py hands on echo_load.cc's the same way).
        "client_before": report.get("client_before"),
        "client_after": report.get("client_after"),
    }
