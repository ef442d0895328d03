#!/usr/bin/env python3
"""Benchmark driver: prints ONE JSON line.

Headline metric (mirrors the reference's headline echo benchmark,
docs/cn/benchmark.md:104 — 2.3 GB/s echo throughput on loopback): large-
payload echo throughput through the full stack over the ICI (registered
shared-memory) transport, with the cross-process shm link and loopback TCP
riding along for comparison.

Round-to-round variance on shared hosts exceeded real deltas in earlier
rounds, so every transport round now runs `REPS` times and reports the
MEDIAN (plus min/max spread for the record). Also included:
  - tail_*: the backup-request tail benchmark (reference benchmark.md:
    126-206 — 2% slow handlers; p99 with backups ≈ backup_ms + p50).
  - scale_*: qps vs caller fibers 1/4/16/64 (reference benchmark.md:110).
  - perf-attribution scrape (ISSUE 6): dispatcher/scheduler counters,
    /status?format=json method stats, and cpu+heap profile snapshots
    saved under profiles/ with their paths committed into the JSON so a
    regression links to evidence.

Regression gate:
  bench.py --compare BENCH_rPREV.json [--current BENCH_rCUR.json]
           [--strict] [--threshold 0.15]
prints per-metric deltas vs the previous round (running the bench first
unless --current names an existing JSON) and exits non-zero past the
threshold ONLY with --strict — the verify flow runs it non-fatal.
"""
import json
import os
import select
import socket
import sys
import statistics
import subprocess
import tempfile
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
BUILD = REPO / "build"

BASELINE_MBPS = 2300.0  # reference echo throughput (BASELINE.md: 2.3 GB/s)
REPS = 3


def build():
    # One rule, shared with chip_smoke.py and tests/conftest.py: a build/
    # configured for another checkout is discarded, never trusted.
    from brpc_tpu import native

    native.build()


def run_tool(name, args, timeout=300):
    exe = BUILD / name
    if not exe.exists():
        return None
    try:
        proc = subprocess.run(
            [str(exe)] + args, capture_output=True, text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return None
    if proc.returncode != 0:
        return None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def median_rounds(args, reps=REPS):
    """Run echo_bench `reps` times; median-combine the numeric fields."""
    runs = [r for r in (run_tool("echo_bench", args) for _ in range(reps))
            if r is not None]
    if not runs:
        return None, 0
    combined = {}
    for key in runs[0]:
        vals = [r[key] for r in runs if key in r]
        combined[key] = statistics.median(vals)
    return combined, len(runs)


def device_path():
    """Framed payloads host->HBM->host through the pipelined DMA staging
    ring (brpc_tpu/device_path.py, ISSUE 9): depth-4 ring, 1MB chunks,
    serial-vs-pipelined interleaved medians. A subprocess because a chip
    belongs to one process: this works only while bench.py itself stays
    off jax."""
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "brpc_tpu.device_path",
             "8", "12", "4", "1020"],
            capture_output=True, text=True, timeout=300, cwd=str(REPO),
        )
    except subprocess.TimeoutExpired:
        return None
    if proc.returncode != 0:
        return None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def perf_attrib_scrape(port):
    """ISSUE 6: scrape the performance-attribution surfaces of a node
    under load — dispatcher/scheduler families, machine-readable method
    status, and cpu+heap profile snapshots (paths land in the BENCH json
    so a regression links to evidence)."""
    out = {}
    # Sample aggressively for the snapshot window; restore the node's
    # OWN prior interval afterwards even if a scrape step dies (the cpu
    # profile fetch is the likeliest to time out).
    prev_interval = None
    try:
        flag = _http(port, "/flags/heap_profiler_sample_bytes")
        prev_interval = int(flag.split(" = ")[1].split()[0])
    except Exception:
        pass
    try:
        _http(port, "/flags/heap_profiler_sample_bytes?setvalue=16384")
        status = json.loads(_http(port, "/status?format=json"))
        methods = status.get("methods", {})
        if methods:
            name, st = sorted(methods.items())[0]
            out["status_json_method"] = name
            out["status_json_qps"] = st.get("qps", 0)
        metrics = _http(port, "/metrics")
        for family, key in (
            ("rpc_dispatcher_epoll_waits", "dispatcher_epoll_waits"),
            ("rpc_dispatcher_events", "dispatcher_events"),
            ("rpc_dispatcher_wakeups", "dispatcher_wakeups"),
            ("rpc_dispatcher_inline_dispatches", "inline_dispatches"),
            ("rpc_dispatcher_inline_overflows", "inline_overflows"),
            ("rpc_server_inline_handlers", "inline_handlers"),
            ("rpc_socket_coalesced_writes", "coalesced_writes"),
            ("rpc_scheduler_steals", "scheduler_steals"),
            ("rpc_socket_write_batch_bytes_count", "socket_write_batches"),
        ):
            total = 0.0
            for line in metrics.splitlines():
                if line.startswith(family + "{") or \
                        line.startswith(family + " "):
                    try:
                        total += float(line.rsplit(" ", 1)[1])
                    except ValueError:
                        pass
            out[key] = int(total)
        profdir = REPO / "profiles"
        profdir.mkdir(exist_ok=True)
        heap = _http(port, "/hotspots/heap?raw=1", timeout=20)
        if "--- maps ---" in heap:
            path = profdir / "bench_heap_latest.prof"
            path.write_text(heap)
            out["heap_profile_path"] = str(path.relative_to(REPO))
        cpu = _http(port, "/hotspots/cpu?seconds=1", timeout=30)
        if "cpu profile:" in cpu:
            path = profdir / "bench_cpu_latest.prof"
            path.write_text(cpu)
            out["cpu_profile_path"] = str(path.relative_to(REPO))
    except Exception:
        pass
    finally:
        if prev_interval is not None:
            try:
                _http(port, "/flags/heap_profiler_sample_bytes?setvalue=%d"
                      % prev_interval)
            except Exception:
                pass
    return out


def _http(port, path, timeout=5):
    url = "http://127.0.0.1:%d%s" % (port, path)
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _spawn_node_ready(node, port, peers, extra_args=(), timeout_s=20.0):
    """Boot one mesh_node and wait for its READY line. Returns
    (proc, ready): the caller always owns proc teardown (its finally
    reaps it whether or not READY ever arrived)."""
    proc = subprocess.Popen(
        [str(node), "--port", str(port), "--peers", str(peers)]
        + list(extra_args),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
    )
    deadline = time.time() + timeout_s
    buf = b""
    while b"READY" not in buf:
        remain = deadline - time.time()
        if remain <= 0:
            return proc, False
        r, _, _ = select.select([proc.stdout], [], [], remain)
        if not r:
            return proc, False
        chunk = os.read(proc.stdout.fileno(), 4096)
        if not chunk:
            return proc, False
        buf += chunk
    return proc, True


def series_scrape():
    """Time-series trajectory for the BENCH record: boot one mesh_node,
    drive it with rpc_press --metrics_csv, then scrape the server's own
    /vars?series= ring — both the client-side per-second qps/p99 rows and
    the server-side 60s qps ring land in the JSON (trends, not just one
    number)."""
    node = BUILD / "mesh_node"
    press = BUILD / "rpc_press"
    if not node.exists() or not press.exists():
        return None
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = None
    try:
        with tempfile.TemporaryDirectory() as td:
            peers = Path(td) / "peers"
            peers.write_text("127.0.0.1:%d\n" % port)
            csv = Path(td) / "press.csv"
            proc, ready = _spawn_node_ready(node, port, peers)
            if not ready:
                return None
            # Generator config mirrored into the BENCH record (ISSUE 7):
            # a qps number is only comparable round-to-round if the load
            # shape that produced it is pinned alongside it.
            press_cfg = {"press_gen_threads": 2, "press_gen_callers": 4,
                         "press_gen_qps": 500, "press_gen_payload": 128}
            subprocess.run(
                [str(press), "--server=127.0.0.1:%d" % port,
                 "--qps=%d" % press_cfg["press_gen_qps"],
                 "--duration_s=4",
                 "--payload=%d" % press_cfg["press_gen_payload"],
                 "--callers=%d" % press_cfg["press_gen_callers"],
                 "--press_threads=%d" % press_cfg["press_gen_threads"],
                 "--metrics_csv=%s" % csv],
                capture_output=True, timeout=60,
            )
            time.sleep(1.2)  # let the 1Hz series sampler tick once more
            url = ("http://127.0.0.1:%d/vars?series="
                   "benchpb_EchoService_Echo_qps" % port)
            with urllib.request.urlopen(url, timeout=5) as r:
                ring = json.loads(r.read().decode())
            out = perf_attrib_scrape(port)
            rows = [r for r in csv.read_text().splitlines()[1:] if r]
            if rows:
                cols = [r.split(",") for r in rows]
                out["press_qps_series"] = [int(float(c[1])) for c in cols]
                out["press_p99_us_series"] = [int(float(c[3])) for c in cols]
            second = ring.get("second", [])
            if second:
                out["server_qps_series_tail"] = [
                    int(v) for v in second[-10:]]
            # Attach the generator config only to a real scrape: a fully
            # failed one must still return None (record skipped), not a
            # metrics-free dict of press_gen_* constants.
            if out:
                out.update(press_cfg)
            return out or None
    except Exception:
        return None
    finally:
        if proc is not None:
            try:
                proc.stdin.close()
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
                proc.wait()  # reap: no zombie holding the port


def _spawn_ready_argv(argv, timeout_s=20.0):
    """Boot a binary with an explicit argv and wait for its READY line
    (infer_server takes positional port + long flags, not the mesh_node
    --port/--peers shape _spawn_node_ready assumes)."""
    proc = subprocess.Popen(
        [str(a) for a in argv],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
    )
    deadline = time.time() + timeout_s
    buf = b""
    while b"READY" not in buf:
        remain = deadline - time.time()
        if remain <= 0:
            return proc, False
        r, _, _ = select.select([proc.stdout], [], [], remain)
        if not r:
            return proc, False
        chunk = os.read(proc.stdout.fileno(), 4096)
        if not chunk:
            return proc, False
        buf += chunk
    return proc, True


def _reap(proc):
    if proc is None:
        return
    try:
        proc.kill()
    except Exception:
        pass
    try:
        proc.wait(timeout=10)
    except Exception:
        pass


def infer_scrape():
    """Continuous micro-batching round (ISSUE 17): boot the
    examples/infer_server serve plane and drive it with rpc_press
    --stream_tokens through the resumable push-stream tier.

    Three phases on fresh servers:
      1. batched — tokens/s, TTFT p50/p99, inter-token p99 (the
         compared serving metrics);
      2. unbatched baseline (--unbatched: one sequence per device
         step) — same load, the deliberately-serial number the batched
         rate is read against;
      3. resume — SIGTERM + restart the server mid-stream; the presses'
         seq-contiguity assertion makes infer_stream_resume_loss a real
         exactly-once proof, and it MUST stay 0.
    """
    server = BUILD / "infer_server"
    press = BUILD / "rpc_press"
    if not server.exists() or not press.exists():
        return None

    def one_press(port, duration_s, tokens=32):
        r = subprocess.run(
            [str(press), "--server=127.0.0.1:%d" % port,
             "--stream_tokens=%d" % tokens, "--qps=400",
             "--duration_s=%d" % duration_s, "--callers=8",
             "--timeout_ms=3000", "--json"],
            capture_output=True, timeout=duration_s + 60)
        lines = [l for l in r.stdout.decode().splitlines()
                 if l.startswith("{")]
        return json.loads(lines[-1]) if lines else None

    def fresh_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    proc = None
    try:
        # --- batched serving --------------------------------------
        port = fresh_port()
        proc, ready = _spawn_ready_argv(
            [server, port, "--step_us", 2000, "--max_batch", 8])
        if not ready:
            return None
        dur = 5
        rep = one_press(port, dur)
        _reap(proc)
        proc = None
        if rep is None or rep.get("press_stream_tokens", 0) <= 0:
            return None
        out = {
            "infer_batched_tokens_per_s": int(
                rep["press_stream_tokens"] / dur),
            "infer_ttft_p50_us": int(rep["press_ttft_us"]["p50"]),
            "infer_ttft_p99_us": int(rep["press_ttft_us"]["p99"]),
            "infer_itl_p99_us": int(rep["press_itl_us"]["p99"]),
        }

        # --- unbatched baseline -----------------------------------
        port = fresh_port()
        proc, ready = _spawn_ready_argv(
            [server, port, "--step_us", 2000, "--max_batch", 8,
             "--unbatched"])
        if ready:
            urep = one_press(port, dur)
            if urep is not None and \
                    urep.get("press_stream_tokens", 0) > 0:
                ups = int(urep["press_stream_tokens"] / dur)
                out["infer_unbatched_tokens_per_s"] = ups
                if ups > 0:
                    out["infer_batch_ratio"] = round(
                        out["infer_batched_tokens_per_s"] / ups, 2)
        _reap(proc)
        proc = None

        # --- restart mid-stream: exactly-once across the resume ---
        port = fresh_port()
        proc, ready = _spawn_ready_argv(
            [server, port, "--step_us", 2000, "--max_batch", 8])
        if ready:
            pp = subprocess.Popen(
                [str(press), "--server=127.0.0.1:%d" % port,
                 "--stream_tokens=64", "--qps=8", "--duration_s=8",
                 "--callers=4", "--timeout_ms=3000", "--json"],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL)
            time.sleep(3.0)  # streams in flight
            _reap(proc)
            proc, ready = _spawn_ready_argv(
                [server, port, "--step_us", 2000, "--max_batch", 8])
            pout, _ = pp.communicate(timeout=90)
            lines = [l for l in pout.decode().splitlines()
                     if l.startswith("{")]
            if ready and lines:
                rrep = json.loads(lines[-1])
                out["infer_stream_resumes"] = int(
                    rrep.get("press_stream_resumes", 0))
                # Lost/duplicated/corrupt tokens across the restart:
                # the acceptance gate — MUST stay 0.
                out["infer_stream_resume_loss"] = int(
                    rrep.get("press_stream_seq_errors", 0))
        return out
    except Exception:
        return None
    finally:
        _reap(proc)


class _CollNode:
    """One mesh_node handle for the collective round: line-buffered
    stdout reads (READY / COLL lines) + stdin commands."""

    def __init__(self, binary, port, peers, extra=()):
        self.proc = subprocess.Popen(
            [str(binary), "--port", str(port), "--peers", str(peers),
             "--collective"] + list(extra),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
        )
        self.buf = b""

    def readline(self, deadline):
        while b"\n" not in self.buf:
            remain = deadline - time.time()
            if remain <= 0:
                return None
            r, _, _ = select.select([self.proc.stdout], [], [], remain)
            if not r:
                return None
            chunk = os.read(self.proc.stdout.fileno(), 4096)
            if not chunk:
                return None
            self.buf += chunk
        line, self.buf = self.buf.split(b"\n", 1)
        return line.decode()

    def wait_ready(self, timeout=20.0):
        deadline = time.time() + timeout
        while True:
            line = self.readline(deadline)
            if line is None:
                return False
            if line.startswith("READY"):
                return True

    def send(self, line):
        self.proc.stdin.write(line.encode() + b"\n")
        self.proc.stdin.flush()

    def coll_line(self, deadline):
        while True:
            line = self.readline(deadline)
            if line is None:
                return None
            if line.startswith("COLL "):
                return json.loads(line[5:])


def collective_scrape():
    """ISSUE 13: pod-scale collectives on the 8-process mesh. Drives
    chunked-pipelined all-reduce / all-gather / all-to-all rounds (and
    the serial unpipelined all-reduce baseline) through the mesh_node
    collective driver and records per-algorithm bus bandwidth — the
    busbw of a round is the SLOWEST node's (the collective is only done
    when everyone is), and the headline acceptance ratio is pipelined
    all-reduce vs the serial fan-in measured by the same driver."""
    node = BUILD / "mesh_node"
    if not node.exists():
        return None
    num = 8
    socks, ports = [], []
    for _ in range(num):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    nodes = []
    try:
        with tempfile.TemporaryDirectory() as td:
            peers = Path(td) / "peers"
            peers.write_text("".join("127.0.0.1:%d\n" % p for p in ports))
            # Append one at a time: a spawn failure mid-list must leave
            # the already-started nodes in `nodes` for the finally reap.
            for p in ports:
                nodes.append(_CollNode(node, p, peers))
            for n in nodes:
                if not n.wait_ready():
                    return None
            time.sleep(2.0)  # shm links + pool handshakes

            seq = [10]  # command rounds share one increasing seq space

            def round_once(alg, nbytes):
                seq[0] += 1
                for n in nodes:
                    n.send("coll %s %d %d" % (alg, nbytes, seq[0]))
                deadline = time.time() + 90.0
                reps = [n.coll_line(deadline) for n in nodes]
                if any(r is None or not r.get("ok") or
                       not r.get("verified") for r in reps):
                    return None
                return reps

            def busbw(alg, nbytes, reps=REPS):
                vals, fallbacks = [], 0
                for _ in range(reps):
                    rs = round_once(alg, nbytes)
                    if rs is None:
                        return None, fallbacks
                    vals.append(min(r["busbw_mbps"] for r in rs))
                    fallbacks += sum(
                        r.get("desc_fallback_chunks", 0) for r in rs)
                return statistics.median(vals), fallbacks

            out = {}
            ar, ar_fb = busbw("allreduce", 4 << 20)
            ag, ag_fb = busbw("allgather", 512 << 10)
            a2a, a2a_fb = busbw("alltoall", 256 << 10)
            serial, _ = busbw("allreduce_serial", 4 << 20)
            if ar is None:
                return None
            out["coll_allreduce_busbw_mbps"] = round(ar, 1)
            if ag is not None:
                out["coll_allgather_busbw_mbps"] = round(ag, 1)
            if a2a is not None:
                out["coll_alltoall_busbw_mbps"] = round(a2a, 1)
            if serial is not None and serial > 0:
                out["coll_allreduce_serial_mbps"] = round(serial, 1)
                # The acceptance gate: chunked-pipelined >= 1.5x serial.
                out["coll_allreduce_pipeline_ratio"] = round(
                    ar / serial, 2)
            out["coll_nranks"] = num
            # Zero inline payload bytes on the descriptor path (the
            # serial baseline is inline BY DESIGN and never attempts
            # descriptors, so it cannot contribute fallbacks).
            out["coll_zero_inline"] = int(
                ar_fb + ag_fb + a2a_fb == 0)
            return out
    except Exception:
        return None
    finally:
        for n in nodes:
            try:
                n.proc.stdin.close()
                n.proc.wait(timeout=10)
            except Exception:
                try:
                    n.proc.kill()
                    n.proc.wait()
                except Exception:
                    pass


def dcn_collective_scrape():
    """ISSUE 14: hierarchical vs flat all-reduce on an emulated-DCN
    two-pod topology. Two mesh groups of 3 nodes; intra-pod links are
    shm, cross-pod links dcn-tier with -dcn_emu_* WAN shaping (10 ms +
    25 MB/s per connection, both directions — a real cross-DC RTT class). The flat ring drags every
    boundary-crossing step through the emulated WAN (per-step latency x
    2(N-1) steps + the full reduced volume over the boundary edges);
    the hierarchical composition crosses it once per leader — the
    acceptance gate is hier busbw >= flat on this topology
    (coll_hier_vs_flat_ratio >= 1.0)."""
    node = BUILD / "mesh_node"
    if not node.exists():
        return None
    pod = 3
    socks, ports = [], []
    for _ in range(2 * pod):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    pod_a, pod_b = ports[:pod], ports[pod:]
    nodes = []
    try:
        with tempfile.TemporaryDirectory() as td:
            naming = Path(td) / "naming"
            naming.write_text(
                "".join("127.0.0.1:%d zone=A\n" % p for p in pod_a)
                + "".join("127.0.0.1:%d zone=B\n" % p for p in pod_b))
            dcn_a = Path(td) / "dcn_a"
            dcn_a.write_text(
                "".join("127.0.0.1:%d zone=B\n" % p for p in pod_b))
            dcn_b = Path(td) / "dcn_b"
            dcn_b.write_text(
                "".join("127.0.0.1:%d zone=A\n" % p for p in pod_a))
            shaping = ["--flag", "dcn_emu_latency_us=10000",
                       "--flag", "dcn_emu_mbps=25"]
            for i, p in enumerate(ports):
                in_a = i < pod
                nodes.append(_CollNode(
                    node, p, naming,
                    extra=["--zone", "A" if in_a else "B",
                           "--dcn_peers",
                           str(dcn_a if in_a else dcn_b)] + shaping))
            for n in nodes:
                if not n.wait_ready():
                    return None
            time.sleep(3.0)  # shm + probed dcn links

            seq = [500]

            def round_once(alg, nbytes):
                seq[0] += 1
                for n in nodes:
                    n.send("coll %s %d %d" % (alg, nbytes, seq[0]))
                deadline = time.time() + 120.0
                reps = [n.coll_line(deadline) for n in nodes]
                if any(r is None or not r.get("ok") or
                       not r.get("verified") or
                       r.get("nranks") != 2 * pod for r in reps):
                    return None
                return min(r["busbw_mbps"] for r in reps)

            def busbw(alg, nbytes, reps=3):
                vals = []
                for _ in range(reps):
                    v = round_once(alg, nbytes)
                    if v is None:
                        return None
                    vals.append(v)
                return statistics.median(vals)

            # 512 KiB: large enough that bandwidth matters, small
            # enough that the flat ring's 2(N-1) latency-synchronized
            # steps dominate over CPU noise on small containers — the
            # regime the hierarchical composition exists for.
            payload = 512 << 10
            flat = busbw("allreduce", payload)
            hier = busbw("hier_allreduce", payload)
            if flat is None or hier is None:
                return None
            out = {
                "coll_flat_dcn_allreduce_busbw_mbps": round(flat, 1),
                "coll_hier_allreduce_busbw_mbps": round(hier, 1),
                "coll_hier_vs_flat_ratio": round(hier / flat, 2)
                if flat > 0 else 0.0,
                "coll_dcn_pods": 2,
            }
            return out
    except Exception:
        return None
    finally:
        for n in nodes:
            try:
                n.proc.stdin.close()
                n.proc.wait(timeout=10)
            except Exception:
                try:
                    n.proc.kill()
                    n.proc.wait()
                except Exception:
                    pass


def verbs_scrape():
    """ISSUE 18: verbs-backed collective exchange vs per-chunk RPCs on
    the same mesh. Four --collective nodes; commanded rounds are lane-
    pinned by alg name — `allreduce_verbs` posts ONE scatter-gather
    REMOTE_WRITE per ring step into the successor's leased pool window
    (plus a sync doorbell), `allreduce_chunks` forces the per-chunk
    descriptor-RPC exchange the verbs lane replaces. The recorded
    ratio is the acceptance gate (>= 1.0: one SGL verb per step must
    not be slower than N chunk RPCs), and the verbs rounds' zero-
    fallback counter proves the lane really ran one-sided instead of
    silently degrading to the chunk path."""
    node = BUILD / "mesh_node"
    if not node.exists():
        return None
    num = 4
    socks, ports = [], []
    for _ in range(num):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    nodes = []
    try:
        with tempfile.TemporaryDirectory() as td:
            peers = Path(td) / "peers"
            peers.write_text("".join("127.0.0.1:%d\n" % p for p in ports))
            for p in ports:
                nodes.append(_CollNode(node, p, peers))
            for n in nodes:
                if not n.wait_ready():
                    return None
            time.sleep(2.0)  # shm links + pool handshakes

            seq = [400]  # distinct command-seq space from other rounds

            def round_once(alg, nbytes):
                seq[0] += 1
                for n in nodes:
                    n.send("coll %s %d %d" % (alg, nbytes, seq[0]))
                deadline = time.time() + 90.0
                reps = [n.coll_line(deadline) for n in nodes]
                if any(r is None or not r.get("ok") or
                       not r.get("verified") for r in reps):
                    return None
                return reps

            def busbw(alg, nbytes, reps=REPS):
                """Median-of-reps slowest-node busbw + the verb lane's
                step/fallback evidence summed over every round."""
                vals, steps, fallbacks = [], 0, 0
                for _ in range(reps):
                    rs = round_once(alg, nbytes)
                    if rs is None:
                        return None, steps, fallbacks
                    vals.append(min(r["busbw_mbps"] for r in rs))
                    steps += sum(r.get("verb_steps", 0) for r in rs)
                    fallbacks += sum(
                        r.get("verb_fallback_chunks", 0) for r in rs)
                return statistics.median(vals), steps, fallbacks

            verbs, vsteps, vfall = busbw("allreduce_verbs", 4 << 20)
            chunk, _, _ = busbw("allreduce_chunks", 4 << 20)
            if verbs is None or chunk is None or chunk <= 0:
                return None
            return {
                "coll_verbs_busbw_mbps": round(verbs, 1),
                "coll_chunk_busbw_mbps": round(chunk, 1),
                "coll_verbs_vs_chunk_ratio": round(verbs / chunk, 2),
                "coll_verbs_steps": vsteps,
                "coll_verbs_zero_fallback": int(vfall == 0),
                "coll_verbs_nranks": num,
            }
    except Exception:
        return None
    finally:
        for n in nodes:
            try:
                n.proc.stdin.close()
                n.proc.wait(timeout=10)
            except Exception:
                try:
                    n.proc.kill()
                    n.proc.wait()
                except Exception:
                    pass


def qos_isolation_scrape():
    """QoS isolation trajectory (ISSUE 8): boot one mesh_node with
    tenant quotas, run one mixed-tenant press where bronze floods at 8x
    its quota while gold trickles at high priority, and record gold's
    qps/p99 plus bronze's shed count — the BENCH record then tracks
    whether isolation holds round over round (gold_p99 is a real
    lower-is-better metric for --compare; bronze counters are context).
    """
    node = BUILD / "mesh_node"
    press = BUILD / "rpc_press"
    if not node.exists() or not press.exists():
        return None
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = None
    try:
        with tempfile.TemporaryDirectory() as td:
            peers = Path(td) / "peers"
            peers.write_text("127.0.0.1:%d\n" % port)
            proc, ready = _spawn_node_ready(
                node, port, peers,
                ["--flag", "rpc_qos_enabled=true", "--flag",
                 "rpc_tenant_quotas=bronze:qps=250,burst=50,w=1,conc=4;"
                 "gold:w=8"])
            if not ready:
                return None
            res = subprocess.run(
                [str(press), "--server=127.0.0.1:%d" % port,
                 "--tenants=gold:1:7,bronze:10:1", "--qps=2200",
                 "--duration_s=3", "--callers=12", "--max_retry=0",
                 "--payload=128", "--json"],
                capture_output=True, timeout=60, text=True,
            )
            line = None
            for ln in reversed(res.stdout.splitlines()):
                if ln.startswith("{"):
                    line = json.loads(ln)
                    break
            if line is None or "press_tenants" not in line:
                return None
            gold = line["press_tenants"].get("gold", {})
            bronze = line["press_tenants"].get("bronze", {})
            return {
                "qos_gold_qps": int(gold.get("qps", 0)),
                "qos_gold_p99_us": int(gold.get("p99_us", 0)),
                "qos_gold_failed": int(gold.get("failed", 0)),
                "qos_bronze_qps": int(bronze.get("qps", 0)),
                "qos_bronze_shed": int(bronze.get("shed", 0)),
            }
    except Exception:
        return None
    finally:
        if proc is not None:
            try:
                proc.stdin.close()
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
                proc.wait()


def qos_cost_scrape():
    """Work-priced admission round (ISSUE 15): bronze floods 64KiB
    bodies INSIDE its request-count rate (a shape a request-counting
    door admits wholesale) while gold trickles light.
    qos_cost_gold_p99_us is the compared isolation metric; bronze's
    shed volume and the server's learned cost estimate are context.
    Boots its OWN node: -rpc_tenant_quotas only applies at server
    start (cost units, no conc= — the gradient limiter owns
    concurrency), and a fresh node keeps the request-count round's
    learned state out of this measurement."""
    node = BUILD / "mesh_node"
    press = BUILD / "rpc_press"
    if not node.exists() or not press.exists():
        return None
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = None
    try:
        with tempfile.TemporaryDirectory() as td:
            peers = Path(td) / "peers"
            peers.write_text("127.0.0.1:%d\n" % port)
            proc, ready = _spawn_node_ready(
                node, port, peers,
                ["--flag", "rpc_qos_enabled=true", "--flag",
                 "rpc_tenant_quotas=bronze:qps=400,burst=100,w=1;"
                 "gold:w=8"])
            if not ready:
                return None
            res = subprocess.run(
                [str(press), "--server=127.0.0.1:%d" % port,
                 "--tenants=gold:4:7:128,bronze:7:1:65536", "--qps=550",
                 "--duration_s=3", "--callers=12", "--max_retry=0",
                 "--json"],
                capture_output=True, timeout=90, text=True,
            )
            line = None
            for ln in reversed(res.stdout.splitlines()):
                if ln.startswith("{"):
                    line = json.loads(ln)
                    break
            if line is None or "press_tenants" not in line:
                return None
            gold = line["press_tenants"].get("gold", {})
            bronze = line["press_tenants"].get("bronze", {})
            tj = json.loads(
                urllib.request.urlopen(
                    "http://127.0.0.1:%d/tenants?format=json" % port,
                    timeout=5).read().decode())
            srv_bronze = tj.get("tenants", {}).get("bronze", {})
            return {
                "qos_cost_gold_p99_us": int(gold.get("p99_us", 0)),
                "qos_cost_gold_qps": int(gold.get("qps", 0)),
                "qos_cost_bronze_shed": int(bronze.get("shed", 0)),
                "qos_cost_bronze_ewma_milli": int(
                    srv_bronze.get("cost_ewma_milli", 0)),
                "qos_cost_backoff_ms_max": int(
                    line.get("press_backoff_ms_max", 0)),
            }
    except Exception:
        return None
    finally:
        if proc is not None:
            try:
                proc.stdin.close()
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
                proc.wait()


def blackbox_scrape():
    """Flight-recorder overhead round (ISSUE 19): the always-on event
    rings must be effectively free on the RPC hot path. One mesh_node
    serves an unthrottled press with the recorder live-toggled OFF then
    ON per rep (the /flags/flight_recorder_enabled portal — same
    process, same sockets, so nothing but the Record gate differs) and
    blackbox_overhead_pct is the relative qps delta of the interleaved
    medians. It is ACCEPTANCE evidence (<= 5), not a compared metric:
    it re-derives from two same-process measurements whose noise floor
    on a shared container exceeds the true per-event cost, so it is
    skip-keyed along with the qps pair and the event-volume context."""
    node = BUILD / "mesh_node"
    press = BUILD / "rpc_press"
    if not node.exists() or not press.exists():
        return None
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    proc = None
    try:
        with tempfile.TemporaryDirectory() as td:
            peers = Path(td) / "peers"
            peers.write_text("127.0.0.1:%d\n" % port)
            proc, ready = _spawn_node_ready(node, port, peers)
            if not ready:
                return None

            def press_qps():
                res = subprocess.run(
                    [str(press), "--server=127.0.0.1:%d" % port,
                     "--qps=8000", "--duration_s=2", "--callers=8",
                     "--press_threads=2", "--payload=128",
                     "--max_retry=0", "--json"],
                    capture_output=True, timeout=60, text=True,
                )
                for ln in reversed(res.stdout.splitlines()):
                    if ln.startswith("{"):
                        return float(json.loads(ln)["press_qps"])
                return None

            def toggle(on):
                _http(port, "/flags/flight_recorder_enabled?setvalue="
                      + ("true" if on else "false"))

            def events():
                return int(float(_http(
                    port, "/vars/rpc_blackbox_events").split()[-1]))

            press_qps()  # warm connections + fiber pool before timing
            off_qps, on_qps, ev_delta = [], [], 0
            for _ in range(REPS):
                toggle(False)
                q = press_qps()
                if q is None:
                    return None
                off_qps.append(q)
                toggle(True)
                e0 = events()
                q = press_qps()
                if q is None:
                    return None
                on_qps.append(q)
                ev_delta += events() - e0
            toggle(True)  # leave the recorder in its always-on default
            off_m = statistics.median(off_qps)
            on_m = statistics.median(on_qps)
            if off_m <= 0:
                return None
            return {
                "blackbox_overhead_pct": round(
                    max(0.0, (off_m - on_m) / off_m * 100.0), 2),
                "blackbox_qps_on": int(on_m),
                "blackbox_qps_off": int(off_m),
                "blackbox_events_per_s": int(ev_delta / (2.0 * REPS)),
            }
    except Exception:
        return None
    finally:
        if proc is not None:
            try:
                proc.stdin.close()
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
                proc.wait()


# Compare-mode metric directions: latency-ish keys regress UP, the rest
# (throughput/qps/counts) regress DOWN. Non-numeric values, series
# arrays, evidence paths, and derived ratios are skipped — as are the
# raw attribution ACTIVITY counters (epoll waits, steals, write
# batches, point-in-time qps): they are context for reading a
# regression, not quality metrics with a better-direction (write
# coalescing LOWERS socket_write_batches at identical throughput and
# must not flag as a regression).
_SKIP_KEYS = {"metric", "unit", "vs_baseline", "reps", "error",
              "status_json_method", "heap_profile_path",
              "cpu_profile_path", "dispatcher_epoll_waits",
              "dispatcher_events", "dispatcher_wakeups",
              "inline_dispatches", "inline_overflows", "inline_handlers",
              "coalesced_writes", "scheduler_steals",
              "socket_write_batches", "status_json_qps",
              "press_gen_threads", "press_gen_callers", "press_gen_qps",
              "press_gen_payload",
              # QoS context counters: bronze's achieved volumes depend on
              # the flood shape and how hard it is shed, not on code
              # quality — gold qps/p99 are the compared isolation metrics.
              "qos_bronze_shed", "qos_bronze_qps", "qos_gold_failed",
              # Work-priced round (ISSUE 15): qos_cost_gold_p99_us /
              # qos_cost_gold_qps ARE compared (isolation under a
              # mixed-COST flood); shed volume, the learned estimate,
              # and the backoff hint are flood-shape context.
              "qos_cost_bronze_shed", "qos_cost_bronze_ewma_milli",
              "qos_cost_backoff_ms_max",
              # Device ring (ISSUE 9): device_path_gbps is THE compared
              # metric. device_path_mbps is the RETIRED pre-ring key —
              # skip-keyed so the MB/s -> GB/s unit change never flags as
              # a regression against old records; ring shape/efficiency
              # numbers are run context (overlap_eff depends on host core
              # availability, not code quality), and booleans are not
              # magnitudes.
              "device_path_mbps", "device_path_serial_gbps",
              "device_path_overlap_eff", "device_path_ring_depth",
              "device_path_chunk_bytes", "device_path_inflight_highwater",
              "device_path_ok", "device_path_registered_staging",
              "device_path_cores", "pool_desc_calls", "pool_desc_bytes",
              "pool_desc_zero_copy",
              # Response-direction descriptor round (ISSUE 12):
              # pool_desc_rsp_mbps IS compared (the symmetric-zero-copy
              # rate); shape/boolean evidence keys are not magnitudes.
              "pool_desc_rsp_calls", "pool_desc_rsp_zero_copy",
              "pool_desc_rsp_inline_bytes",
              # Lease leak gauges (ISSUE 10): evidence, not a rate — a
              # healthy round records pinned_after == 0; reaped counts
              # chaos/crash reclamations, so neither is a compare metric.
              "pool_desc_pinned_after", "pool_desc_reaped",
              # Collective round (ISSUE 13): the three coll_*_busbw_mbps
              # keys ARE compared (higher better). The serial baseline
              # and the derived pipeline ratio are context — the serial
              # number measures the deliberately-unpipelined path, and
              # the ratio re-derives from two compared/contextual keys;
              # nranks is shape, zero_inline a boolean proof.
              "coll_allreduce_serial_mbps", "coll_allreduce_pipeline_ratio",
              "coll_nranks", "coll_zero_inline",
              # Emulated-DCN round (ISSUE 14): the hier busbw IS
              # compared; the flat number measures the deliberately-WAN-
              # dragged baseline on an emulated pipe, and the ratio
              # re-derives from the two (the >= 1.0 acceptance lives in
              # the verify recipe); pod count is shape.
              "coll_flat_dcn_allreduce_busbw_mbps",
              "coll_hier_vs_flat_ratio", "coll_dcn_pods",
              # One-sided verbs round (ISSUE 18): coll_verbs_busbw_mbps
              # IS compared (higher better). The chunk number measures
              # the deliberately-two-sided baseline, the ratio
              # re-derives from the two (its >= 1.0 acceptance lives in
              # the verify recipe), steps/nranks are shape, and
              # zero_fallback is a boolean proof.
              "coll_chunk_busbw_mbps", "coll_verbs_vs_chunk_ratio",
              "coll_verbs_steps", "coll_verbs_zero_fallback",
              "coll_verbs_nranks",
              # Inference-serving round (ISSUE 17): batched tokens/s and
              # the TTFT/ITL latencies ARE compared. The unbatched
              # number measures the deliberately-serial baseline, the
              # ratio re-derives from the two, resume counts are
              # restart-timing context, and resume_loss is a MUST-BE-0
              # acceptance gate (asserted in the verify recipe — a 0->1
              # flip would read as "improved" to the direction
              # heuristic, so it must not be compared).
              "infer_unbatched_tokens_per_s", "infer_batch_ratio",
              "infer_stream_resumes", "infer_stream_resume_loss",
              # Flight-recorder round (ISSUE 19): blackbox_overhead_pct
              # is the <= 5 acceptance gate (asserted in the verify
              # recipe), re-derived from the same-process on/off qps
              # pair — all four keys are evidence/context, and the qps
              # pair must not double-count as throughput metrics (the
              # series round already compares qps).
              "blackbox_overhead_pct", "blackbox_qps_on",
              "blackbox_qps_off", "blackbox_events_per_s"}


def _lower_is_better(key):
    return any(t in key for t in
               ("p50", "p90", "p99", "p999", "_us", "latency"))


def compare_benches(prev_path, cur_path, strict, threshold):
    """Per-metric delta report between two BENCH jsons. Returns the exit
    code: non-zero only when --strict and a regression beyond
    `threshold` exists."""
    def load_bench(path):
        data = json.loads(Path(path).read_text())
        # Committed BENCH_rNN.json files are driver wrappers with the
        # metrics line in "tail"; a raw bench.py line parses directly.
        if isinstance(data.get("tail"), str):
            start = data["tail"].find("{")
            if start >= 0:
                data = json.loads(data["tail"][start:])
        return data

    prev = load_bench(prev_path)
    cur = load_bench(cur_path)
    rows = []
    regressions = []
    for key in sorted(set(prev) & set(cur)):
        if key in _SKIP_KEYS or key.endswith("_series") or \
                key.endswith("_series_tail"):
            continue
        pv, cv = prev[key], cur[key]
        if not isinstance(pv, (int, float)) or \
                not isinstance(cv, (int, float)):
            continue
        if pv == 0:
            delta = 0.0 if cv == 0 else float("inf")
        else:
            delta = (cv - pv) / abs(pv)
        worse = -delta if _lower_is_better(key) else delta
        flag = ""
        if worse < -threshold:
            flag = "REGRESSION"
            regressions.append(key)
        elif worse > threshold:
            flag = "improved"
        rows.append((key, pv, cv, delta, flag))
    print("regression gate: %s -> %s  (threshold %.0f%%, %s)"
          % (prev_path, cur_path, threshold * 100,
             "strict" if strict else "report-only"))
    print("%-28s %14s %14s %9s  %s"
          % ("metric", "prev", "cur", "delta", ""))
    for key, pv, cv, delta, flag in rows:
        print("%-28s %14g %14g %8.1f%%  %s"
              % (key, pv, cv, delta * 100, flag))
    for evidence in ("cpu_profile_path", "heap_profile_path"):
        if cur.get(evidence):
            print("evidence: %s = %s" % (evidence, cur[evidence]))
    if regressions:
        print("%d regression(s): %s" % (len(regressions),
                                        ", ".join(regressions)))
        return 1 if strict else 0
    print("no regressions past threshold")
    return 0


def _arg_value(argv, name):
    if name in argv:
        i = argv.index(name)
        if i + 1 < len(argv):
            return argv[i + 1]
    return None


def main():
    argv = sys.argv[1:]
    prev_path = _arg_value(argv, "--compare")
    if prev_path is not None:
        cur_path = _arg_value(argv, "--current")
        threshold = float(_arg_value(argv, "--threshold") or 0.15)
        strict = "--strict" in argv
        if cur_path is None:
            # No current json: run the bench now, save, then gate.
            import io
            from contextlib import redirect_stdout
            buf = io.StringIO()
            with redirect_stdout(buf):
                run_bench()
            line = buf.getvalue().strip().splitlines()[-1]
            cur = Path(tempfile.gettempdir()) / "BENCH_current.json"
            cur.write_text(line + "\n")
            print(line)
            cur_path = str(cur)
        sys.exit(compare_benches(prev_path, cur_path, strict, threshold))
    run_bench()


def run_bench():
    try:
        build()
    except Exception:
        print(json.dumps({
            "metric": "echo_throughput", "value": 0, "unit": "MB/s",
            "vs_baseline": 0.0, "error": "build failed",
        }))
        return

    ici, ici_n = median_rounds(["--json", "--ici"])
    xproc, _ = median_rounds(["--json", "--xproc"])
    tcp, _ = median_rounds(["--json"])
    tcp_pooled, _ = median_rounds(["--json", "--pooled"])

    if ici is None or "mbps" not in ici:
        # Degraded fallback: loopback TCP only (tail still runs over TCP).
        tail = run_tool("echo_bench", ["--json", "--tail"], timeout=600)
        if tcp is not None and "mbps" in tcp:
            mbps = float(tcp["mbps"])
            out = {
                "metric": "echo_throughput_1MB_loopback",
                "value": round(mbps, 1), "unit": "MB/s",
                "vs_baseline": round(mbps / BASELINE_MBPS, 3),
            }
            if tail is not None:
                out.update(tail)
            print(json.dumps(out))
        else:
            print(json.dumps({
                "metric": "echo_throughput", "value": 0, "unit": "MB/s",
                "vs_baseline": 0.0, "error": "no bench tool built",
            }))
        return

    tail = run_tool("echo_bench", ["--json", "--tail"], timeout=600)
    scale = run_tool("echo_bench", ["--json", "--scale", "--ici"],
                     timeout=600)
    # One-sided descriptor round, BOTH directions (ISSUE 9/12):
    # attachments as pool references over the in-process ici link.
    # pool_desc_mbps / pool_desc_rsp_mbps are the logical rates per
    # direction (the symmetric-zero-copy gate wants rsp within 20% of
    # req); the *_zero_copy booleans are the verification proof.
    pool_desc = run_tool("echo_bench", ["--json", "--ici", "--pool_desc"],
                         timeout=300)
    device = device_path()
    series = series_scrape()
    qos = qos_isolation_scrape()
    qos_cost = qos_cost_scrape()
    coll = collective_scrape()
    dcn_coll = dcn_collective_scrape()
    verbs = verbs_scrape()
    infer = infer_scrape()
    blackbox = blackbox_scrape()

    mbps = float(ici["mbps"])
    out = {
        "metric": "echo_throughput_1MB_ici",
        "value": round(mbps, 1),
        "unit": "MB/s",
        "vs_baseline": round(mbps / BASELINE_MBPS, 3),
        "reps": ici_n,
    }
    for k in ("qps_4k", "p50_us_4k", "p99_us_4k"):
        if k in ici:
            out["ici_" + k] = ici[k]
    for prefix, r in (("xproc_", xproc), ("tcp_", tcp),
                      ("tcp_pooled_", tcp_pooled)):
        if r is not None:
            for k in ("mbps", "qps_4k", "p99_us_4k"):
                if k in r:
                    out[prefix + k] = r[k]
    if tail is not None:
        out.update(tail)
    if scale is not None:
        out.update(scale)
    if pool_desc is not None:
        out.update(pool_desc)
    if device is not None:
        out.update(device)
    if series is not None:
        out.update(series)
    if qos is not None:
        out.update(qos)
    if qos_cost is not None:
        out.update(qos_cost)
    if coll is not None:
        out.update(coll)
    if dcn_coll is not None:
        out.update(dcn_coll)
    if verbs is not None:
        out.update(verbs)
    if infer is not None:
        out.update(infer)
    if blackbox is not None:
        out.update(blackbox)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
