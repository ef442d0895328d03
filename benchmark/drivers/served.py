"""Driver `served`: one server process of the program, one client process
of the benchmark's own (client/echo_load.cc), a closed loop over the shm
link. Both are host-only children; this process holds the chip, which the
served path never touches (ROADMAP A1) -- it sums one payload of the
cell's size on the chip once per run, so that a traced run has a device
plane to read, and says exactly that.
"""
import json
import os
import re
import select
import signal
import subprocess
import tempfile
import time
import urllib.request
from pathlib import Path

import numpy as np

from benchmark import device, payload, stats, tracing

ROOT = Path(__file__).resolve().parent.parent.parent
CLIENT_SRC = ROOT / "benchmark" / "client" / "echo_load.cc"
CONTROL_FLIP_EVERY = 64
GAP_NOTE = "served_path:no_payload_crosses_the_chip(A1)"


class Children:
    """Every child this run starts, each in its own process group, all
    stopped and waited for on exit."""

    def __init__(self):
        self.procs = []

    def __enter__(self):
        return self

    def spawn(self, argv, **kw):
        proc = subprocess.Popen([str(a) for a in argv],
                                start_new_session=True, **kw)
        self.procs.append(proc)
        return proc

    def __exit__(self, *exc):
        for proc in self.procs:
            if proc.poll() is None:
                for sig in (signal.SIGTERM, signal.SIGKILL):
                    try:
                        os.killpg(proc.pid, sig)
                    except ProcessLookupError:
                        break
                    try:
                        proc.wait(timeout=5)
                        break
                    except subprocess.TimeoutExpired:
                        continue
            for pipe in (proc.stdin, proc.stdout, proc.stderr):
                if pipe is not None:
                    pipe.close()
        return False


def read_line(proc, timeout: float, what: str) -> str:
    """One line of the child's stdout, or RuntimeError after `timeout`.
    Read in blocks (the client's report is tens of KB); what a block holds
    beyond the line waits on `proc.unread` for the next call."""
    deadline = time.monotonic() + timeout
    buf = getattr(proc, "unread", b"")
    fd = proc.stdout.fileno()
    while b"\n" not in buf:
        left = deadline - time.monotonic()
        if left <= 0 or not select.select([fd], [], [], left)[0]:
            raise RuntimeError(f"{what}: nothing after {timeout:.0f} s "
                               f"(got {buf[-200:]!r})")
        block = os.read(fd, 1 << 16)
        if not block:
            raise RuntimeError(f"{what}: child ended (rc {proc.poll()}) "
                               f"after {buf[-200:]!r}")
        buf += block
    line, _, proc.unread = buf.partition(b"\n")
    return line.decode().strip()


def build_client(build_dir: Path) -> Path:
    """The load generator, compiled against this checkout's build; reused
    while it is newer than its source and the library."""
    out = build_dir / "echo_load"
    lib = build_dir / "libtpurpc.so"
    if out.exists() and out.stat().st_mtime >= max(
            CLIENT_SRC.stat().st_mtime, lib.stat().st_mtime):
        return out
    tmp = build_dir / f"echo_load.{os.getpid()}.tmp"
    cmd = ["g++", "-std=c++17", "-O2", "-fno-omit-frame-pointer",
           f"-I{ROOT / 'cpp'}", f"-I{build_dir}", str(CLIENT_SRC),
           str(build_dir / "bench_echo.pb.cc"), "-o", str(tmp),
           f"-L{build_dir}", "-ltpurpc", f"-Wl,-rpath,{build_dir}",
           "-lprotobuf", "-lpthread", "-lz"]
    proc = subprocess.run(cmd, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(f"{' '.join(cmd)} failed:\n{proc.stdout[-3000:]}")
    os.replace(tmp, out)  # atomic: two runs may build at once
    return out


# ------------------------------------------------------------- the portal

def http_get(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=10) as r:
        return r.read().decode("utf-8", "replace")


def parse_vars(text: str) -> dict:
    """`name : number` lines of /vars (series and text values are left)."""
    out = {}
    for m in re.finditer(r"^(\w+) : (-?\d+(?:\.\d+)?)\b", text, re.M):
        out[m.group(1)] = float(m.group(2))
    return out


def parse_loops(text: str) -> dict:
    """/loops: per-epoll-loop rows, the run-to-completion counters, and
    per-pool scheduler rows."""
    loops, pools, counters = [], [], {}
    section = None
    for line in text.splitlines():
        if line.startswith("loop "):
            section = "loops"
        elif line.startswith("pool "):
            section = "pools"
        elif line.startswith("inline_dispatches"):
            counters = {k: int(v) for k, v in
                        re.findall(r"(\w+): (-?\d+)", line)}
        elif section == "loops" and re.match(r"^\d", line):
            f = line.split()
            p50, p99, mx = (int(x) for x in f[7].split("/"))
            loops.append({"loop": int(f[0]), "epoll_waits": int(f[2]),
                          "events": int(f[3]), "wakeups": int(f[4]),
                          "wake_to_dispatch_us": {"p50": p50, "p99": p99,
                                                  "max": mx}})
        elif section == "pools" and re.match(r"^\d", line):
            f = line.split()
            pools.append({"pool": int(f[0]), "workers": int(f[1]),
                          "steals": int(f[3]),
                          "runq_highwater": int(f[6])})
        elif not line.strip():
            section = None
    return {"loops": loops, "pools": pools, "counters": counters}


def scrape(port: int) -> dict:
    return {"vars": parse_vars(http_get(port, "/vars")),
            "loops": parse_loops(http_get(port, "/loops")),
            "status": json.loads(http_get(port, "/status?format=json"))}


def proc_cpu_s(pid: int) -> float:
    """utime + stime of a live process, from /proc."""
    with open(f"/proc/{pid}/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


# ------------------------------------------------------ the chip's part

def device_touch(run, nbytes: int):
    """One payload of the cell's size, from the seed, summed on the chip.
    The served path does not cross the chip (A1); this is all the device
    does in these cells, once in set-up (it compiles) and once as the
    window closes, so a traced run has a device plane with an op on it."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu import compile_cache

    compile_cache.enable()
    words = payload.words(run.seed, 1 << 20, nbytes // 4)
    total = jax.jit(jnp.sum)

    def once():
        total(jax.device_put(words, run.devices[0])).block_until_ready()
    once()
    return once


# ----------------------------------------------------------------- run

def judge(report: dict, seed: int, callers: int, nbytes: int) -> list:
    """The numbers compared, each with its limit (all exact: 0)."""
    digests_wrong = int(report["body_crc32"]
                        != payload.bodies_crc32(seed, callers, nbytes))
    for c, (seq, crc) in enumerate(zip(report["last_seq"],
                                       report["last_reply_crc32"])):
        if crc != payload.echo_reply_crc32(seed, c, seq, nbytes):
            digests_wrong += 1
    return [("replies_wrong", report["mismatched"], 0),
            ("replies_missing_or_error", report["rpc_failed"], 0),
            ("digests_wrong", digests_wrong, 0)]


def metrics(report: dict, lat_ns: np.ndarray) -> tuple:
    """(end-to-end values, notes) from the client's report and the full
    latency sample: all verified operations over all the window."""
    window = report["window_s"]
    e2e = {"qps": stats.rate(report["ok"], window),
           "goodput_gbps": stats.gbps(report["ok"] * report["bytes_each"],
                                      window)}
    notes = {}
    if lat_ns.size:
        e2e["p99_us"] = stats.percentile(lat_ns, 0.99) / 1e3
        notes = {"p50_us": stats.percentile(lat_ns, 0.5) / 1e3,
                 "p999_us": stats.percentile(lat_ns, 0.999) / 1e3,
                 "max_us": float(lat_ns.max()) / 1e3,
                 "mean_us": float(lat_ns.mean()) / 1e3,
                 "samples": int(lat_ns.size)}
    return e2e, notes


def run(run) -> dict:
    from brpc_tpu import native

    cfg, tr = run.config, run.traffic
    callers, nbytes = int(tr["callers"]), int(tr["bytes"])
    if tr["loop"] != "closed" or nbytes % 8:
        raise ValueError("the served driver runs closed loops of payloads "
                         "that are a multiple of 8 bytes")
    window = tracing.window_seconds(run.seconds, run.trace)
    build_dir = native.build()
    client_bin = build_client(build_dir)
    run.mark("built")
    server_argv = [ROOT / cfg["server"][0], *cfg["server"][1:]]
    if run.control == "flip_reply":
        server_argv = [client_bin, "--control-server", CONTROL_FLIP_EVERY]
    elif run.control is not None:
        raise ValueError(f"served: unknown control {run.control!r}")

    fd, sample_path = tempfile.mkstemp(prefix="bench-lat-", suffix=".bin")
    os.close(fd)
    try:
        with Children() as kids:
            server = kids.spawn(server_argv, stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE)
            port = int(read_line(server, 30, "server PORT").split()[1])
            client = kids.spawn(
                [client_bin, "--port", port, "--callers", callers,
                 "--bytes", nbytes, "--seed", run.seed, "--seconds", window,
                 "--warm-ms", tr["warm_ms"], "--timeout-ms",
                 cfg["timeout_ms"], "--sample-out", sample_path],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE)
            touch_device = device_touch(run, nbytes)
            run.mark("device_touch_warmed")
            if read_line(client, 60, "client READY") != "READY":
                raise RuntimeError("client did not say READY")
            run.mark("client_ready")
            before = scrape(port)
            http_get(port, "/loops?reset=1")
            cpu0 = proc_cpu_s(server.pid)
            own0 = os.times()
            with tracing.TraceWindow(run.trace) as tw:
                t_first = time.monotonic()
                client.stdin.write(b"GO\n")
                client.stdin.flush()
                report = json.loads(read_line(
                    client, window + cfg["timeout_ms"] / 1e3 + 30,
                    "client result"))
                touch_device()  # at the close: a profiler just started
                #                 missed it in 1 traced run of 5 on the chip
            cpu1 = proc_cpu_s(server.pid)
            own1 = os.times()
            server_threads = len(os.listdir(f"/proc/{server.pid}/task"))
            after = scrape(port)
            server.stdin.close()
            server.stdin = None
            try:
                server.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass  # Children stops it
        lat_ns = np.fromfile(sample_path, dtype="<u8")
    finally:
        os.unlink(sample_path)

    e2e, notes = metrics(report, lat_ns)
    # Where a run reads slow, these say whether the rate sat low throughout
    # or dipped (whole seconds only), and that this process left the cores
    # alone. The chip host's /proc/stat and context-switch counts read 0.
    run.notes.update(
        notes, client_errors=report["errors"],
        client_workers=report["workers"], server_threads=server_threads,
        harness_cpu_cores=(sum(own1[:2]) - sum(own0[:2])) / report["window_s"],
        ops_per_s=report["per_s"][:int(report["window_s"])])
    checks = judge(report, run.seed, callers, nbytes)
    return {
        "attempted": report["attempted"],
        "failed": report["rpc_failed"] + report["mismatched"],
        "window_s": report["window_s"], "t_first_op": t_first,
        "memory_peak_bytes": device.memory_peak_bytes(run.devices),
        "end_to_end": e2e, "checks": checks, "trace": tw.summary(),
        "gap_notes": {GAP_NOTE: tw.t1 - tw.t0},
        "ops": report["ok"], "payload_bytes": report["ok"] * nbytes,
        "client_cpu_s": report["client_cpu_s"],
        "server_cpu_s": cpu1 - cpu0, "before": before, "after": after,
        # The client process's own stage table and counters at the window's
        # two edges, in a scrape's shape; None (never an empty table) from a
        # client that sends none.
        "client_before": report.get("client_before"),
        "client_after": report.get("client_after"),
    }
