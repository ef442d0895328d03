#!/usr/bin/env python3
"""chip_smoke.py — does tpu-rpc still start on the TPU host? (claims nothing)

    python3 chip_smoke.py

Drives the main path once through the entry points a user would call, at
the sizes BASELINE.json's configs name, on every chip `jax.devices()`
reports. Six legs, one JSON line each (every line names the platform,
device kind and device count it ran beside), then one last line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

- build       cmake + ninja into build/ from the checkout (real protoc); a
              build/ configured for another path is discarded.
- served      build/echo_server answers echo_client (reply bytes checked)
              and 5 x `multi_threaded_echo_client HOST 64 4 4096`, is still
              alive and answering /status; then echo_bench --json in --ici,
              --xproc, TCP, --scale --ici, --ici/--xproc --pool_desc, three
              times each (both zero-copy flags 1, pinned_after 0).
- device      brpc_tpu.device_path on EACH chip: 64 MiB through the depth-4
              staging ring in 1 MiB-class chunks (>= 8 passes) and in 4
              MiB-class chunks; every chunk's crc32c against the C++
              framer's, every on-device integrity word against numpy.
- tensor_echo brpc_tpu.tensor_service served in this process on device 0:
              16 calls of tensor.Step at 1 MiB over the shm link, each
              reply (made on the chip) against brpc_tpu.tensor_reference
              (`tensor_echo_ok`).
- kv_put      brpc_tpu.kv_service served in this process on device 0, a pool
              of 2 sessions x 3 layers x 9 MiB: a session's 3 layers put
              (in chunks of kv_service.CHUNK_BYTES, 3 MiB: 3 each, the word
              made on the chip against brpc_tpu.kv_reference), read back
              byte for byte, and evicted whole by the third session
              (`kv_put_ok`).
- collective  __graft_entry__.mesh_data_plane over Mesh(jax.devices()):
              fan-out rows of 4 KiB and 1 MiB, partition shards, all-reduce
              / all-gather / all-to-all at 4 MiB and 64 MiB per rank, framed
              through libtpurpc.so, bit-exact against numpy; entry() jitted
              at its own shape and at 64 x 4 KiB.

Rules it keeps: no fallback that hides the device (not on a TPU -> exit 1,
no result line); ONE process touches jax — this one — and everything it
spawns is a host-only C++ binary; every child has a hard timeout and is
stopped before exit; any failed leg makes the exit status non-zero. GB/s
and seconds printed by the legs are observations, compared with nothing.
"""
import atexit
import faulthandler
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
BUILD = REPO / "build"

# The whole run must fit the driver's 1200 s, compilation included.
TOTAL_LIMIT_S = 1140
# /dev/shm must hold the 64 MiB pool region of BOTH processes of an
# --xproc round plus their link segments; IciBlockPool::Init would fall
# back to anonymous memory with a WARNING and cross-process links refuse.
SHM_NEEDED_BYTES = 160 << 20

_children: list[subprocess.Popen] = []


class LegFailed(Exception):
    """A leg's check did not hold; the message is the reason."""


class _LegTimeout(BaseException):
    """Raised by SIGALRM inside a leg that overran its limit."""


def _stop(proc: subprocess.Popen) -> None:
    """End a child and everything in its process group."""
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
    if proc in _children:
        _children.remove(proc)


def _stop_all() -> None:
    for proc in list(_children):
        _stop(proc)


def _spawn(argv: list[str], **kw) -> subprocess.Popen:
    # Own session: a timeout takes the child's own children with it
    # (echo_bench --xproc forks its server).
    proc = subprocess.Popen(argv, start_new_session=True, **kw)
    _children.append(proc)
    return proc


def run_tool(argv: list[str], timeout: float) -> str:
    """Run one host-only binary to its end; returns its stdout.
    Non-zero exit, a timeout or no output is a failed leg."""
    proc = _spawn(argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                  text=True)
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise LegFailed(f"{' '.join(argv)}: no end after {timeout:.0f}s")
    finally:
        _stop(proc)
    if proc.returncode != 0:
        raise LegFailed(f"{' '.join(argv)}: rc {proc.returncode}: "
                        f"{err.strip()[-600:]}")
    if not out.strip():
        raise LegFailed(f"{' '.join(argv)}: printed no result")
    return out


def json_line(out: str) -> dict:
    for line in out.splitlines():
        if line.lstrip().startswith("{"):
            return json.loads(line)
    raise LegFailed(f"no JSON result line in: {out.strip()[-300:]}")


# ---------------------------------------------------------------- device

def find_tpu() -> dict:
    """The device as jax reports it, or SystemExit: this is the one place
    that decides whether the run may proceed."""
    listed = [p.strip().lower()
              for p in os.environ.get("JAX_PLATFORMS", "").split(",")
              if p.strip()]
    if listed and "tpu" not in listed:
        sys.exit(f"chip_smoke: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']} "
                 "keeps jax off the TPU; this script proves the chip path "
                 "and has no CPU fallback")
    try:
        import jax
        backend = jax.default_backend()
        devices = jax.devices()
    except Exception as e:  # jax raises RuntimeError subclasses of its own
        sys.exit(f"chip_smoke: jax could not start a backend: {e}")
    if backend != "tpu":
        sys.exit(f"chip_smoke: no TPU found: jax.default_backend() is "
                 f"{backend!r} ({devices[0].device_kind})")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


# ------------------------------------------------------------------ legs

def leg_build() -> dict:
    facts = {"nproc": os.cpu_count()}
    for tool in ("cmake", "ninja", "protoc", "g++"):
        try:
            out = subprocess.run([tool, "--version"], capture_output=True,
                                 text=True, timeout=30)
        except FileNotFoundError:
            raise LegFailed(f"{tool} is not installed on this machine")
        facts[tool] = out.stdout.splitlines()[0].strip() if out.stdout else ""
    st = os.statvfs("/dev/shm")
    facts["shm_free_mb"] = (st.f_bavail * st.f_frsize) >> 20
    if st.f_bavail * st.f_frsize < SHM_NEEDED_BYTES:
        raise LegFailed(
            f"/dev/shm has {facts['shm_free_mb']} MiB free, the pool "
            f"regions and link segments need {SHM_NEEDED_BYTES >> 20}")
    with socket.socket() as s:
        try:
            s.bind(("127.0.0.1", 0))
        except OSError as e:
            raise LegFailed(f"127.0.0.1 does not bind: {e}")

    from brpc_tpu import native

    had_build = (BUILD / "build.ninja").exists()
    try:
        native.build(timeout=900)
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        raise LegFailed(str(e))
    facts["cold_build"] = not had_build
    for name in ("libtpurpc.so", "echo_server", "echo_client",
                 "multi_threaded_echo_client", "echo_bench"):
        if not (BUILD / name).exists():
            raise LegFailed(f"build/{name} was not built")
    # The wire codec must be protoc's, generated by this build only.
    strays = [str(p.relative_to(REPO))
              for top in ("cpp", "tools", "examples", "tests", "brpc_tpu")
              for p in (REPO / top).rglob("*.pb.*")]
    if strays:
        raise LegFailed(f"generated protobuf code outside build/: {strays}")
    head = (BUILD / "rpc_meta.pb.h").read_text()[:400]
    if "Generated by the protocol buffer compiler" not in head:
        raise LegFailed("build/rpc_meta.pb.h is not protoc output")
    return facts


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _serve_and_hammer() -> dict:
    port = _free_port()
    addr = f"127.0.0.1:{port}"
    # stderr to a file, not a pipe nobody drains (a full pipe would park
    # the server and read as a hang).
    log = tempfile.TemporaryFile()
    server = _spawn([str(BUILD / "echo_server"), str(port)],
                    stdout=subprocess.DEVNULL, stderr=log)

    def must_be_alive(when: str) -> None:
        if server.poll() is not None:
            log.seek(0)
            raise LegFailed(
                f"echo_server died {when} (rc {server.returncode}): "
                f"{log.read().decode(errors='replace')[-600:]}")

    try:
        deadline = time.monotonic() + 15
        while True:
            must_be_alive("before it listened")
            try:
                socket.create_connection(("127.0.0.1", port), 0.5).close()
                break
            except OSError:
                if time.monotonic() > deadline:
                    raise LegFailed("echo_server never listened")
                time.sleep(0.1)
        out = run_tool([str(BUILD / "echo_client"), addr, "8"], 30)
        if sum(ln.startswith("echo ") for ln in out.splitlines()) != 8:
            raise LegFailed(f"echo_client answered short: {out[-300:]}")
        qps = []
        for round_no in range(1, 6):
            out = run_tool([str(BUILD / "multi_threaded_echo_client"), addr,
                            "64", "4", "4096"], 60)
            last = out.strip().splitlines()[-1]
            if not last.startswith("qps="):
                raise LegFailed(f"no qps line in round {round_no}: {last}")
            qps.append(float(last.split()[0][4:]))
            must_be_alive(f"under client round {round_no}")
        with urllib.request.urlopen(f"http://{addr}/status", timeout=10) as r:
            status = r.read().decode()
        if r.status != 200 or "EchoService" not in status:
            raise LegFailed("/status did not describe the EchoService")
        run_tool([str(BUILD / "echo_client"), addr, "4"], 30)
        must_be_alive("after the client rounds")
        return {"mt_client_qps_4k_64fibers": qps}
    finally:
        _stop(server)
        log.close()


# echo_bench modes, three times each: (label, args, keys that must be > 0,
# keys that must equal a value).
_DESC_OK = {"pool_desc_zero_copy": 1, "pool_desc_rsp_zero_copy": 1,
            "pool_desc_pinned_after": 0}
_BENCH_MODES = (
    ("ici", ["--ici"], ("mbps", "qps_4k"), {}),
    ("xproc", ["--xproc"], ("mbps", "qps_4k"), {}),
    ("tcp", [], ("mbps", "qps_4k"), {}),
    ("scale_ici", ["--scale", "--ici"],
     ("scale_qps_1", "scale_qps_4", "scale_qps_16", "scale_qps_64"), {}),
    ("ici_pool_desc", ["--ici", "--pool_desc"],
     ("pool_desc_mbps", "pool_desc_rsp_mbps"), _DESC_OK),
    ("xproc_pool_desc", ["--xproc", "--pool_desc"],
     ("pool_desc_mbps", "pool_desc_rsp_mbps"), _DESC_OK),
)


def leg_served() -> dict:
    facts = _serve_and_hammer()
    for label, args, positive, exact in _BENCH_MODES:
        seen = {key: [] for key in positive}
        for _ in range(3):
            rec = json_line(run_tool(
                [str(BUILD / "echo_bench"), "--json", *args], 120))
            for key in positive:
                if not rec.get(key, 0) > 0:
                    raise LegFailed(f"echo_bench {label}: {key} = "
                                    f"{rec.get(key)!r}")
            for key, want in exact.items():
                if rec.get(key) != want:
                    raise LegFailed(f"echo_bench {label}: {key} = "
                                    f"{rec.get(key)!r}, want {want}")
            for key in positive:
                seen[key].append(rec[key])
        facts[label] = seen
    return facts


def leg_device() -> dict:
    import jax

    from brpc_tpu import device_path

    chips = []
    for dev in jax.devices():
        rec = {}
        # (label, chunk_kb, reps): 1 MiB-class chunks for >= 8 pipelined
        # passes, then 4 MiB-class chunks; 64 MiB payload, ring depth 4.
        for label, chunk_kb, reps in (("1m", 1020, 8), ("4m", 4092, 2)):
            r = device_path.run(payload_mb=64, reps=reps, ring_depth=4,
                                chunk_kb=chunk_kb, device=dev)
            if not r["device_path_ok"]:
                raise LegFailed(f"{dev}: crc32c or integrity word mismatch "
                                f"({label} chunks): {r}")
            if not r["device_path_registered_staging"]:
                raise LegFailed(f"{dev}: staging ring is not pool memory")
            if not r["device_path_device"].startswith("tpu:"):
                raise LegFailed(f"{dev}: record names "
                                f"{r['device_path_device']}")
            if r["device_path_inflight_highwater"] > 4:
                raise LegFailed(f"{dev}: ring window exceeded: {r}")
            rec[f"gbps_{label}"] = r["device_path_gbps"]
            rec[f"chunk_bytes_{label}"] = r["device_path_chunk_bytes"]
        chips.append({"device": str(dev), "verified": True, **rec})
    return {"chips": chips, "note": "GB/s are observations"}


def leg_tensor_echo() -> dict:
    """tensor.Step served in-process on device 0 (ISSUE 29): 16 calls of
    1 MiB from 4 caller threads over the shm link, every reply held to the
    plain reference. The device leg of a served call, from a bare
    checkout."""
    import threading

    import jax
    import numpy as np

    from brpc_tpu import native, tensor_reference, tensor_service

    key, nbytes, callers, each = 0x5EED1E57, 1 << 20, 4, 4
    dev = jax.devices()[0]
    service = tensor_service.serve(dev, depth=4, max_bytes=nbytes, key=key)
    wrong, errors = [], []

    def caller(c):
        try:
            channel = native.StepChannel(service.port, ici=True)
            try:
                rng = np.random.default_rng(1000 + c)
                for n in range(each):
                    x = rng.integers(0, 256, nbytes, dtype=np.uint8)
                    got = channel.call(x).tobytes()
                    if got != tensor_reference.step(x, key):
                        wrong.append((c, n))
            finally:
                channel.close()
        except Exception as e:  # reported by the leg, on its thread
            errors.append(f"caller {c}: {type(e).__name__}: {e}")

    t0 = time.monotonic()
    try:
        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(callers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        service.close()
    if errors or wrong or service.failure is not None:
        raise LegFailed(f"tensor.Step on {dev}: errors {errors}, replies "
                        f"that differ from the reference {wrong}, service "
                        f"failure {service.failure!r}")
    if dev.platform != "tpu":
        raise LegFailed(f"tensor.Step ran on {dev}")
    return {"tensor_echo_ok": True, "calls": callers * each,
            "bytes_each": nbytes, "device": str(dev),
            "seconds_calls": round(time.monotonic() - t0, 2)}


def leg_kv_put() -> dict:
    """kvpb.Cache served in-process on device 0 (ISSUE 33): three sessions of
    3 layers of 9 MiB into a pool of 2, one call at a time over the shm
    link: every word and admission number the reference's, the first
    session read back byte for byte, then evicted whole by the third. The
    multi-chunk, stateful device leg of a served call, from a bare
    checkout."""
    import jax
    import numpy as np

    from brpc_tpu import kv_reference, kv_service, native

    layers, slots, nbytes = 3, 2, 9 << 20
    dev = jax.devices()[0]
    service = kv_service.serve(dev, layers=layers, sessions=slots,
                               layer_bytes=nbytes)
    ref = kv_reference.Cache(slots, layers, nbytes)
    rng = np.random.default_rng(33)
    wrong = []
    t0 = time.monotonic()
    try:
        channel = native.StepChannel(service.port, ici=True)
        try:
            for session in (101, 202, 303):
                for layer in range(layers):
                    x = rng.integers(0, 256, nbytes, dtype=np.uint8)
                    if channel.put(session, layer, x) != ref.put(
                            session, layer, x):
                        wrong.append(("put", session, layer))
                if session == 101:
                    for layer in range(layers):
                        got = channel.get(session, layer, nbytes).tobytes()
                        if got != ref.get(session, layer):
                            wrong.append(("get", session, layer))
            for layer in range(layers):  # 303 took 101's slot
                try:
                    channel.get(101, layer, nbytes)
                    wrong.append(("evicted and answered", 101, layer))
                except native.RpcError as e:
                    if e.code != native.KV_NOT_FOUND:
                        raise
            if channel.get(303, 2, nbytes).tobytes() != ref.get(303, 2):
                wrong.append(("get", 303, 2))
        finally:
            channel.close()
    finally:
        service.close()
    if wrong or service.failure is not None or list(service.table) != list(
            ref.slots):
        raise LegFailed(f"kvpb.Cache on {dev}: differs from the reference "
                        f"at {wrong}, table {list(service.table)} against "
                        f"{list(ref.slots)}, service failure "
                        f"{service.failure!r}")
    if dev.platform != "tpu":
        raise LegFailed(f"kvpb.Cache ran on {dev}")
    return {"kv_put_ok": True, "puts": 3 * layers, "bytes_each": nbytes,
            "chunks_each": nbytes // kv_service.CHUNK_BYTES,
            "pool_bytes": service.pool_bytes, "device": str(dev),
            "seconds_calls": round(time.monotonic() - t0, 2)}


def leg_collective() -> dict:
    import jax
    import numpy as np

    from __graft_entry__ import entry, mesh_data_plane
    from brpc_tpu.parallel import reference as ref

    devices = jax.devices()
    mesh = jax.sharding.Mesh(np.array(devices), ("peers",))
    facts = {"mesh_devices": len(devices)}
    # (rows, bulk per rank): 4 KiB rows + 4 MiB, then 1 MiB rows + 64 MiB.
    for label, row_words, bulk_words in (("4k_4m", 1 << 10, 1 << 20),
                                         ("1m_64m", 1 << 18, 1 << 24)):
        t0 = time.monotonic()
        try:
            facts[f"ops_{label}"] = mesh_data_plane(
                mesh, row_words, bulk_words, require_native=True)
        except (RuntimeError, FileNotFoundError) as e:
            raise LegFailed(f"mesh_data_plane {label}: {e}")
        facts[f"seconds_{label}"] = round(time.monotonic() - t0, 2)

    fn, (example,) = entry()
    for payloads in (np.asarray(example), ref.fill_rows(9, 64, 1024)):
        checks, lengths, echoed = jax.jit(fn)(payloads)
        if (not np.array_equal(np.asarray(echoed), payloads)
                or not np.array_equal(np.asarray(checks),
                                      ref.row_checksums(payloads))
                or set(np.asarray(lengths).tolist()) != {4096}):
            raise LegFailed(f"entry() at {payloads.shape} differs from "
                            "the numpy reference")
        if next(iter(echoed.devices())).platform != "tpu":
            raise LegFailed("entry() did not run on the TPU")
    facts["entry_shapes"] = [[8, 1024], [64, 1024]]
    return facts


LEGS = (("build", 900, leg_build), ("served", 600, leg_served),
        ("device", 400, leg_device), ("tensor_echo", 200, leg_tensor_echo),
        ("kv_put", 200, leg_kv_put), ("collective", 400, leg_collective))


# ---------------------------------------------------------------- runner

def _on_alarm(signum, frame):
    raise _LegTimeout()


def run_legs(legs, device: dict) -> int:
    """Run every leg under its time limit, one JSON line each; returns the
    exit status: 0 only if every leg passed."""
    started = time.monotonic()
    failed = []
    signal.signal(signal.SIGALRM, _on_alarm)
    for name, limit, fn in legs:
        left = TOTAL_LIMIT_S - (time.monotonic() - started)
        limit = max(1, int(min(limit, left)))
        line = {"leg": name, "platform": device["platform"],
                "device_kind": device["kind"],
                "device_count": device["count"]}
        t0 = time.monotonic()
        # SIGALRM fails the leg with its reason where Python still runs; a
        # call stuck in native code gets every thread's stack and a hard
        # exit 30 s later instead.
        try:
            signal.alarm(limit)
            faulthandler.dump_traceback_later(limit + 30, exit=True,
                                              file=sys.__stderr__)
            line.update(ok=True, **fn())
        except LegFailed as e:
            line.update(ok=False, error=str(e))
        except _LegTimeout:
            line.update(ok=False, error=f"timed out after {limit}s")
        except Exception as e:  # a leg must never take the others' report
            line.update(ok=False, error=f"{type(e).__name__}: {e}")
        finally:
            signal.alarm(0)
            faulthandler.cancel_dump_traceback_later()
            _stop_all()
        line["seconds"] = round(time.monotonic() - t0, 1)
        if not line["ok"]:
            failed.append(name)
        print(json.dumps(line), flush=True)
    if failed:
        print(f"chip_smoke: FAILED legs: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


def main() -> int:
    if not (REPO / "brpc_tpu").is_dir() or not (REPO / "CMakeLists.txt").exists():
        sys.exit(f"chip_smoke: {REPO} holds no tpu-rpc checkout to prove")
    sys.path.insert(0, str(REPO))
    device = find_tpu()
    atexit.register(_stop_all)

    from brpc_tpu import compile_cache

    cache_dir = compile_cache.enable()
    before = compile_cache.entry_count(cache_dir)
    print(f"chip_smoke: {device['count']} x {device['kind']} "
          f"({device['platform']}); compile cache {cache_dir}: "
          f"{before} entries before", flush=True)
    t0 = time.monotonic()
    status = run_legs(LEGS, device)
    after = compile_cache.entry_count(cache_dir)
    print(f"chip_smoke: compile cache {cache_dir}: {after} entries after "
          f"(+{after - before}); wall {time.monotonic() - t0:.1f}s",
          flush=True)
    if status == 0:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
