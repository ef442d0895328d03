"""The stage clock on the served path (ISSUE 25): `echo_bench --ici-server`
under a short closed-loop client round over the shm link, then the server's
own portal must show

  * every per-call server stage counted once per request served: the
    five stages between "first bytes consumed" and "reply posted", whose
    sum is the call's residence in the server (each ends on the stamp the
    next starts from);
  * `tici.link_handoff` from stamps that crossed the process boundary in
    `Desc::pad` (an unstamped or wrapped one would read ~71 minutes);
  * the same table as text on /status and as a histogram family on /metrics
    (held to the exposition lint), the safety-net counters on /vars;
  * /rpcz phases still ordered, now that they take the stage clock's stamps;
  * with 1 MiB messages pipelined on the one connection, no call longer
    inside the server than its caller waited for it.

Host-only; the client is the benchmark's own load generator. Every wait
on a child has its own timeout (`served.read_line`).
"""
import json
import re
import subprocess

import numpy as np

from test_chaos_soak import _http_get
from test_metrics_lint import _lint_exposition

METHOD = "benchpb.EchoService.Echo"
PER_CALL = ["tnet.consume_to_cut", "tfiber.dispatch_to_handler",
            "trpc.handler", "trpc.respond", "tnet.write_queue"]
SAFETY_NETS = ["rpc_scheduler_park_timeouts", "rpc_link_credit_wait_timeouts",
               "rpc_socket_epollout_timeouts"]


def _round(served, client_bin, port, tmp_path, seconds, nbytes=4096,
           at_go=lambda: None):
    """One closed-loop round of the load generator (4 callers, one
    connection); its report, with the window's latencies in us. `at_go`
    runs after the warm-up, before the window."""
    sample = tmp_path / "lat.bin"
    with served.Children() as kids:
        client = kids.spawn(
            [client_bin, "--port", port, "--callers", 4, "--bytes", nbytes,
             "--seed", 25, "--seconds", seconds, "--warm-ms", 100,
             "--timeout-ms", 10000, "--sample-out", sample],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        assert served.read_line(client, 60, "client READY") == "READY"
        at_go()
        client.stdin.write(b"GO\n")
        client.stdin.flush()
        report = json.loads(served.read_line(client, seconds + 40,
                                             "client result"))
    report["latency_us"] = np.fromfile(sample, dtype="<u8") / 1000.0
    return report


def test_server_stages_count_every_request_and_telescope(cpp_build,
                                                         tmp_path):
    from benchmark.drivers import served

    client_bin = served.build_client(cpp_build)
    with served.Children() as kids:
        server = kids.spawn([cpp_build / "echo_bench", "--ici-server"],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        port = int(served.read_line(server, 30, "server PORT").split()[1])
        report = _round(served, client_bin, port, tmp_path, 1.0)
        assert report["rpc_failed"] == 0 and report["ok"] > 100

        status = json.loads(_http_get(port, "/status?format=json"))
        served_n = status["methods"][METHOD]["count"]
        assert served_n >= report["ok"]  # the warm-up's calls too
        stages = status["stages"]
        for name in PER_CALL:
            assert stages[name]["count"] == served_n, (name, stages[name])
            assert sum(n for _, n in stages[name]["buckets"]) == served_n
        # Residence (the five's sum) is time the callers waited: under
        # their own mean, and not nothing.
        residence = sum(stages[n]["sum_us"] for n in PER_CALL) / served_n
        assert 0 < residence < report["latency_us"].mean()
        link = stages["tici.link_handoff"]
        assert link["count"] >= served_n  # one sample a descriptor
        assert 0 <= link["max_us"] < 10_000_000, link
        assert stages["tfiber.wake_to_run"]["count"] > 0
        # The client's stages stay empty in the server's process.
        for name in ("trpc.issue", "trpc.match", "trpc.caller_wake"):
            assert stages[name]["count"] == 0
        assert "trpc.server_residence" not in stages  # derived by readers
        assert "test.only" not in stages

        text = _http_get(port, "/status")
        assert "stages (us, cumulative since start)" in text
        assert re.search(r"^  tici\.link_handoff +\d+ ", text, re.M), text

        metrics = _http_get(port, "/metrics")
        families, errors = _lint_exposition(metrics)
        assert not errors, "\n".join(errors)
        assert families.get("rpc_stage_us") == "histogram"
        assert re.search(
            r'^rpc_stage_us_count\{stage="tnet.write_queue"\} %d$'
            % served_n, metrics, re.M)

        vars_text = _http_get(port, "/vars")
        for name in SAFETY_NETS:
            for var in (name, name + "_found_work"):
                assert re.search(r"^%s : \d+\b" % var, vars_text, re.M), var

        # rpcz takes its phases from the same stamps: still ordered.
        _http_get(port, "/flags/enable_rpcz?setvalue=true")
        _round(served, client_bin, port, tmp_path, 0.3)
        spans = json.loads(_http_get(port, "/rpcz?format=json"))["spans"]
        mine = [s for s in spans if s["kind"] == "SERVER"
                and s["method"] == METHOD]
        assert mine, spans[:3]
        for s in mine:
            assert (0 < s["start_us"] <= s["process_start_us"]
                    <= s["process_end_us"] <= s["end_us"]), s
        server.stdin.close()
        server.stdin = None
        server.wait(timeout=10)


def test_pipelined_large_messages_start_their_clock_at_their_own_bytes(
        cpp_build, tmp_path):
    """Four callers keep 1 MiB requests in flight on one connection, so a
    read often brings the tail of one message with the head of the next
    and the server's buffer is seldom empty between them. The stamps must
    stay sane there: every call counted once, the copy through the link
    inside the residence, the residence inside what the caller waited.
    (That a message left behind by a cut takes the stamp of the read that
    brought it, not its predecessor's, is held exactly by cpp_tests
    Net.AMessageLeftBehindByACutStartsItsClockAtItsOwnBytes; here the
    two differ by a tenth, less than this host's run-to-run range.)"""
    from benchmark import stages as window_stages
    from benchmark.drivers import served

    client_bin = served.build_client(cpp_build)
    with served.Children() as kids:
        server = kids.spawn([cpp_build / "echo_bench", "--ici-server"],
                            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        port = int(served.read_line(server, 30, "server PORT").split()[1])
        obs = {}

        def scrape(edge):
            obs[edge] = {"status": json.loads(
                _http_get(port, "/status?format=json"))}

        report = _round(served, client_bin, port, tmp_path, 1.5,
                        nbytes=1 << 20, at_go=lambda: scrape("before"))
        scrape("after")
        assert report["rpc_failed"] == 0 and report["ok"] > 50
        observed = report["latency_us"]
        cut = window_stages.window(obs, "tnet.consume_to_cut")
        # The window's calls, give or take those in flight at its edges.
        assert abs(cut["count"] - observed.size) <= 8
        residence = window_stages.residence_mean_us(obs)
        assert 0 < cut["sum_us"] / cut["count"] < residence
        assert residence < observed.mean(), (residence, observed.mean())
        server.stdin.close()
        server.stdin = None
        server.wait(timeout=10)
