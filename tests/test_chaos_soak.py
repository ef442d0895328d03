"""Multi-process mesh chaos soak (VERDICT r5 item 3).

Eight `mesh_node` processes form a full mesh: every node is an echo
server AND a client of every peer over (a) shared-memory ICI links and
(b) an rr load-balanced channel whose membership comes from a file://
naming service. Mid-run the soak

  * SIGKILLs one node (host failure),
  * partitions another via the deterministic fault-injection layer
    (each node's /chaos portal page, drop=1.0 scoped per-peer),
  * heals the partition and restarts the killed node.

Asserted invariants:
  * every issued RPC terminates (sync callers + outstanding==0 at stop);
  * zero lost completions (issued == ok + failed per node and plane);
  * the circuit breaker isolated the flapping peer and the health check
    revived it (rpc_circuit_breaker_isolations / rpc_health_check_revives
    in /vars);
  * nodes shut down cleanly (exit 0 — Server::Join quiesces all sockets,
    so a leaked socket or hung fiber turns into a timeout/exit failure).
"""
import json
import os
import select
import socket
import subprocess
import time
import urllib.parse
import urllib.request

NUM_NODES = 8

# Soak-tuned robustness knobs: small breaker windows + fast health checks
# so isolation->revival cycles fit the soak's seconds-scale windows.
NODE_FLAGS = [
    "circuit_breaker_short_window_size=8",
    "circuit_breaker_short_window_error_percent=20",
    "circuit_breaker_long_window_size=64",
    "circuit_breaker_min_isolation_duration_ms=100",
    "circuit_breaker_max_isolation_duration_ms=1000",
    "ns_health_check_interval_ms=300",
]


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _http_get(port, path, timeout=5.0):
    url = "http://127.0.0.1:%d%s" % (port, path)
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def _var(port, name):
    """Numeric /vars value; 0 when the var does not exist (yet)."""
    try:
        text = _http_get(port, "/vars/" + name)
    except Exception:
        return 0
    try:
        return int(text.rsplit(":", 1)[-1].strip())
    except ValueError:
        return 0


class Node:
    def __init__(self, binary, port, idx, peers_file, flags=NODE_FLAGS,
                 extra_args=()):
        self.port = port
        self.idx = idx
        self.proc = subprocess.Popen(
            [str(binary), "--port", str(port), "--id", str(idx), "--peers",
             str(peers_file)]
            + list(extra_args)
            + [arg for f in flags for arg in ("--flag", f)],
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
        )
        self._buf = b""

    def send(self, line):
        self.proc.stdin.write(line.encode() + b"\n")
        self.proc.stdin.flush()

    def _readline(self, deadline):
        while b"\n" not in self._buf:
            remain = deadline - time.time()
            if remain <= 0:
                return None
            r, _, _ = select.select([self.proc.stdout], [], [], remain)
            if not r:
                return None
            chunk = os.read(self.proc.stdout.fileno(), 4096)
            if not chunk:
                return None
            self._buf += chunk
        line, self._buf = self._buf.split(b"\n", 1)
        return line.decode()

    def wait_ready(self, timeout=30.0):
        deadline = time.time() + timeout
        while True:
            line = self._readline(deadline)
            if line is None:
                return False
            if line.startswith("READY"):
                return True

    def stop_and_report(self, timeout=30.0):
        try:
            self.proc.stdin.write(b"stop\n")
            self.proc.stdin.flush()
        except (BrokenPipeError, OSError):
            # The node died mid-run — exactly what the soak exists to
            # catch; surface WHO and HOW instead of an opaque pipe error.
            raise AssertionError(
                "node %d (port %d) died before drain: exit=%s"
                % (self.idx, self.port, self.proc.poll()))
        deadline = time.time() + timeout
        while True:
            line = self._readline(deadline)
            if line is None:
                return None
            if line.startswith("REPORT "):
                return json.loads(line[len("REPORT "):])

    def shutdown(self, timeout=30.0):
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        return self.proc.wait(timeout=timeout)

    def kill9(self):
        self.proc.kill()
        self.proc.wait()


def _chaos(port, **params):
    q = urllib.parse.urlencode(params)
    return _http_get(port, "/chaos?" + q)


def test_mesh_chaos_soak(cpp_build, tmp_path):
    binary = cpp_build / "mesh_node"
    assert binary.exists(), "mesh_node not built"
    ports = _free_ports(NUM_NODES)
    peers_file = tmp_path / "mesh_members"
    peers_file.write_text("".join("127.0.0.1:%d\n" % p for p in ports))

    nodes = [Node(binary, ports[i], i, peers_file) for i in range(NUM_NODES)]
    try:
        for n in nodes:
            assert n.wait_ready(), "node %d never became ready" % n.idx

        time.sleep(3.0)  # healthy warm-up traffic

        # --- inject: kill node 3, partition node 5 --------------------
        kill_idx, part_idx = 3, 5
        nodes[kill_idx].kill9()

        part_ep = "127.0.0.1:%d" % ports[part_idx]
        others = ",".join(
            "127.0.0.1:%d" % p for i, p in enumerate(ports)
            if i not in (kill_idx, part_idx))
        # Bidirectional partition through per-peer scoping: node 5 drops
        # its client-side traffic to everyone; everyone drops theirs to
        # node 5. Control-plane HTTP (ephemeral remote ports) and the
        # raw health-check probes are unaffected by design — so the
        # breaker flaps isolate->revive, exactly the cycle under test.
        _chaos(ports[part_idx], enable=1, seed=1000 + part_idx,
               plan="drop=1.0", peers=others)
        for i, p in enumerate(ports):
            if i in (kill_idx, part_idx):
                continue
            _chaos(p, enable=1, seed=1000 + i, plan="drop=1.0",
                   peers=part_ep)

        # Wait (bounded) for the breaker to isolate and the health check
        # to revive somewhere in the mesh — the partitioned node's own
        # calls all time out, so its breaker trips within a few call
        # timeouts; polling beats a fixed sleep on a loaded 1-core host.
        alive = [i for i in range(NUM_NODES) if i != kill_idx]
        isolations = revives = 0
        deadline = time.time() + 25.0
        while time.time() < deadline:
            isolations = sum(
                _var(ports[i], "rpc_circuit_breaker_isolations")
                for i in alive)
            revives = sum(_var(ports[i], "rpc_health_check_revives")
                          for i in alive)
            if isolations >= 1 and revives >= 1:
                break
            time.sleep(1.0)
        assert isolations >= 1, "circuit breaker never isolated the peer"
        assert revives >= 1, "health check never revived an isolated peer"

        # --- heal: chaos off everywhere, restart the killed node ------
        for i in alive:
            _chaos(ports[i], enable=0)
        nodes[kill_idx] = Node(binary, ports[kill_idx], kill_idx, peers_file)
        assert nodes[kill_idx].wait_ready()

        time.sleep(6.0)  # mesh links re-establish; traffic recovers

        # --- drain + invariants ---------------------------------------
        reports = []
        for n in nodes:
            rep = n.stop_and_report()
            assert rep is not None, "node %d produced no report" % n.idx
            reports.append(rep)

        total_ok = 0
        for rep in reports:
            # Zero lost completions: everything issued terminated.
            assert rep["outstanding"] == 0, rep
            assert rep["lb_issued"] == rep["lb_ok"] + rep["lb_failed"], rep
            assert rep["shm_issued"] == rep["shm_ok"] + rep["shm_failed"], rep
            total_ok += rep["lb_ok"] + rep["shm_ok"]
        # The mesh kept serving through kill + partition + heal.
        assert total_ok > 100, reports
        # The restarted node rejoined and did useful work.
        restarted = reports[kill_idx]
        assert restarted["lb_ok"] + restarted["shm_ok"] > 0, restarted
        # Peers re-established at least one shm link to the restarted
        # node (its death failed their pinned sockets).
        assert sum(r["reconnects"] for r in reports) >= 1, reports

        # Clean teardown: exit 0 requires Server::Join to quiesce every
        # socket — leaks show up as a hang (timeout) or non-zero exit.
        for n in nodes:
            assert n.shutdown() == 0, "node %d unclean exit" % n.idx
    finally:
        for n in nodes:
            try:
                n.proc.kill()
            except OSError:
                pass


def test_deadline_budget_soak(cpp_build, tmp_path):
    """Delay-heavy phase: deadline propagation + retry budgets (ISSUE 2).

    Three nodes; mid-run every handler starts sleeping 50 ms while a
    stale-traffic fiber issues budget-starved calls (1 ms / 30 ms
    deadlines, both below the learned ~50 ms service time -> shed by the
    TimeoutConcurrencyLimiter at admission), a raw probe fiber sends
    handcrafted frames stamped timeout_ms=0 (the wire shape of a client
    that already gave up -> expired-on-arrival shed), every server fails
    three in ten of the calls it admitted with a retriable error, and one
    node gets reset-chaos on its client side too, to provoke retries
    against the configured retry budget.

    Asserted:
      * expired requests are SHED, not executed (rpc_server_expired_requests
        / rpc_server_shed_requests grow; stale executions stay a minority);
      * total re-issues stay within the configured retry budget
        (burst + ratio * successes, per channel) and
        rpc_retry_budget_exhausted is observable;
      * zero lost completions on every plane, clean exit 0.
    """
    num = 3
    budget_tokens = 20
    budget_ratio = 0.1
    binary = cpp_build / "mesh_node"
    assert binary.exists(), "mesh_node not built"
    ports = _free_ports(num)
    peers_file = tmp_path / "mesh_members"
    peers_file.write_text("".join("127.0.0.1:%d\n" % p for p in ports))

    flags = NODE_FLAGS + [
        "rpc_retry_budget_tokens=%d" % budget_tokens,
        "rpc_retry_budget_ratio=%g" % budget_ratio,
        # Every stale call fails BY DESIGN (that's the point of the
        # phase); with the soak-tightened breaker windows those errors
        # would isolate healthy servers and starve the shed counters.
        # Breaker isolate/revive cycles are the kill+partition soak's
        # subject, not this one's.
        "enable_circuit_breaker=false",
    ]
    nodes = [
        Node(binary, ports[i], i, peers_file, flags=flags,
             extra_args=("--timeout_cl_ms", "800"))
        for i in range(num)
    ]
    try:
        for n in nodes:
            assert n.wait_ready(), "node %d never became ready" % n.idx

        time.sleep(2.0)  # healthy traffic; EMA learns the fast latency

        # --- delay-heavy phase -----------------------------------------
        for n in nodes:
            n.send("delay 50 30")
        # What drains the budget: every server answers 30 % of the calls
        # it admitted TERR_OVERCROWDED (the handler seam: retriable, and
        # with all three alike the outlier tier has no one to route
        # around), so an LB call wants 0.3 re-issues where a success earns
        # 0.1 back and the 20 tokens are gone in about a hundred calls.
        # Reset-chaos on node 2's client side alone never did that (ISSUE
        # 31): health checks route around a connection that resets, one
        # call in 2-3 s failed retryably against 6-8 tokens a second
        # earned back, and rpc_retry_budget_exhausted stayed 0. It stays in
        # the phase for the connection-level failures and reconnects.
        for p in ports[:2]:
            _chaos(p, enable=1, seed=4242, plan="error_rate=0.3")
        others = ",".join(
            "127.0.0.1:%d" % p for i, p in enumerate(ports) if i != 2)
        _chaos(ports[2], enable=1, seed=4242,
               plan="reset=0.3,error_rate=0.3", peers=others)

        # Shedding and budget exhaustion become observable within the
        # phase (bounded poll beats a fixed sleep on a loaded host).
        deadline = time.time() + 30.0
        expired = shed = exhausted = 0
        while time.time() < deadline:
            expired = sum(
                _var(p, "rpc_server_expired_requests") for p in ports)
            shed = sum(_var(p, "rpc_server_shed_requests") for p in ports)
            exhausted = sum(
                _var(p, "rpc_retry_budget_exhausted") for p in ports)
            if expired >= 5 and shed >= 5 and exhausted >= 1:
                break
            time.sleep(1.0)
        assert expired >= 5, "expired-on-arrival requests were not shed"
        assert shed >= 5, "budget-below-service-time requests were not shed"
        assert exhausted >= 1, "retry budget never exhausted under chaos"

        # --- heal + drain ----------------------------------------------
        for p in ports:
            _chaos(p, enable=0)
        for n in nodes:
            n.send("delay 0 0")
        time.sleep(1.5)

        # Read per-process re-issue counters BEFORE stopping traffic
        # is unnecessary — the processes (and /vars) stay alive until
        # shutdown; reports first, then vars.
        reports = []
        for n in nodes:
            rep = n.stop_and_report()
            assert rep is not None, "node %d produced no report" % n.idx
            reports.append(rep)

        for i, rep in enumerate(reports):
            # Zero lost completions on every plane, stale included.
            assert rep["outstanding"] == 0, rep
            assert rep["lb_issued"] == rep["lb_ok"] + rep["lb_failed"], rep
            assert rep["shm_issued"] == rep["shm_ok"] + rep["shm_failed"], rep
            assert rep["stale_issued"] == (
                rep["stale_ok"] + rep["stale_failed"]), rep
            # The server dropped (expired/shed) most stale calls instead
            # of executing work nobody reads.
            assert rep["stale_issued"] > 20, rep
            assert rep["stale_executed"] <= rep["stale_issued"] // 2, rep
            # Re-issues bounded by the configured budget: one LB channel
            # + (num-1) shm channels per node, each reconnect is a fresh
            # channel (fresh burst), plus ratio * successes earned back.
            ok = rep["lb_ok"] + rep["shm_ok"] + rep["stale_ok"]
            channels = 1 + (num - 1) + rep["reconnects"]
            bound = channels * budget_tokens + budget_ratio * ok + 50
            reissues = (_var(ports[i], "rpc_client_retries")
                        + _var(ports[i], "rpc_client_backup_requests"))
            assert reissues <= bound, (
                "node %d re-issued %d times, budget bound %.0f (%s)"
                % (i, reissues, bound, rep))

        for n in nodes:
            assert n.shutdown() == 0, "node %d unclean exit" % n.idx
    finally:
        for n in nodes:
            try:
                n.proc.kill()
            except OSError:
                pass
