"""Performance attribution surfaces (ISSUE 6): drives echo load through
one mesh_node and asserts the data-plane attribution layer is populated
and lint-clean:

  * /hotspots/heap and /hotspots/growth serve symbolized pprof-style
    text under load, and ?raw=1 is the offline-symbolizable dump
    (weighted stacks + /proc/self/maps);
  * /loops shows per-epoll-loop wake/dispatch telemetry and per-pool
    scheduler counters with non-zero activity;
  * /connections carries the per-socket I/O attribution columns
    (in/out Bps, write batches, queued-write high-water, EOVERCROWDED);
  * /status?format=json is the machine-readable MethodStatus;
  * the new prometheus families pass the exposition lint and feed
    /vars?series= rings.
"""
import json
import time

from test_chaos_soak import Node, _free_ports, _http_get
from test_metrics_lint import _lint_exposition


def _section_rows(text, header_token):
    """Rows of the /loops table whose header contains `header_token`,
    each a dict from the header's column names to the row's values (the
    leading one-word columns; a column is found by its name, never by its
    position)."""
    rows = []
    header = None
    for line in text.splitlines():
        if header_token in line:
            header = line.split()
            continue
        if header is not None:
            if not line.strip():
                header = None
                continue
            parts = line.split()
            if parts and parts[0].isdigit():
                rows.append(dict(zip(header, parts)))
    return rows


def test_perf_attribution_surfaces(cpp_build, tmp_path):
    binary = cpp_build / "mesh_node"
    assert binary.exists(), "mesh_node not built"
    (port,) = _free_ports(1)
    peers_file = tmp_path / "peers"
    peers_file.write_text("127.0.0.1:%d\n" % port)
    node = Node(binary, port, 0, peers_file)
    try:
        assert node.wait_ready(), "node never became ready"
        # Tighten the sampling interval so the node's own echo traffic
        # produces heap samples within the soak window.
        _http_get(port, "/flags/heap_profiler_sample_bytes?setvalue=8192")
        time.sleep(3.0)  # self-echo traffic + the 1Hz series sampler

        # ---- heap / growth profiler ----
        heap = _http_get(port, "/hotspots/heap")
        assert heap.startswith("heap profile:"), heap[:200]
        raw = _http_get(port, "/hotspots/heap?raw=1")
        assert "--- maps ---" in raw, raw[:200]
        stack_lines = [l for l in raw.splitlines() if " @ " in l]
        assert stack_lines, "no sampled stacks under load:\n" + raw[:400]
        # Weighted rows: "<bytes> <count> @ pc...", bytes >= count > 0.
        first = stack_lines[0].split()
        assert int(first[0]) >= int(first[1]) > 0, stack_lines[0]
        growth = _http_get(port, "/hotspots/growth")
        assert growth.startswith("growth profile:"), growth[:200]

        # ---- /loops: dispatcher + scheduler telemetry ----
        loops = _http_get(port, "/loops")
        disp = _section_rows(loops, "epoll_waits")
        assert disp, "no dispatcher rows:\n" + loops
        # Wakes and events summed ACROSS loops: sockets shard by fd, so
        # on a multi-loop host any single loop may legitimately be idle.
        assert sum(int(r["epoll_waits"]) for r in disp) > 0, loops
        assert sum(int(r["events"]) for r in disp) > 0, loops
        pools = _section_rows(loops, "runq_highwater")
        assert pools, "no scheduler pool rows:\n" + loops
        assert int(pools[0]["workers"]) > 0, loops

        # ---- /connections: per-socket I/O attribution ----
        header = _http_get(port, "/connections").splitlines()[0]
        for col in ("in_Bps", "out_Bps", "wr_batches", "avg_batch",
                    "q_hiwater", "crowded"):
            assert col in header, header
        time.sleep(1.0)
        rows = [l.split() for l in
                _http_get(port, "/connections").splitlines()[1:] if l]
        assert rows, "no connections under self-traffic"
        # Scrape-to-scrape rate: the self-echo peer connection moves
        # bytes, so some socket shows a non-zero in or out rate.
        assert any(float(r[5]) > 0 or float(r[6]) > 0 for r in rows), rows
        # ...and writev batching is attributed.
        assert any(int(r[7]) > 0 for r in rows), rows

        # ---- /status?format=json ----
        st = json.loads(_http_get(port, "/status?format=json"))
        assert st["draining"] == 0
        assert st["methods"], st
        method = next(iter(st["methods"].values()))
        for key in ("count", "qps", "concurrency", "errors", "rejected",
                    "expired", "shed", "latency_us"):
            assert key in method, method
        assert method["count"] > 0, st
        assert "p99" in method["latency_us"], method

        # ---- prometheus families + series rings ----
        text = _http_get(port, "/metrics")
        families, errors = _lint_exposition(text)
        assert not errors, "exposition lint failed:\n" + "\n".join(errors)
        assert families.get("rpc_dispatcher_epoll_waits") == "gauge", \
            sorted(families)
        assert families.get("rpc_dispatcher_events_per_wake") == "summary"
        assert families.get("rpc_scheduler_steals") == "gauge"
        assert families.get("rpc_scheduler_runqueue_highwater") == "gauge"
        assert families.get("rpc_socket_write_batch_bytes") == "summary"
        assert 'rpc_dispatcher_epoll_waits{loop="0"}' in text, text[:500]
        ring = json.loads(_http_get(
            port, "/vars?series=rpc_dispatcher_epoll_waits_loop_0"))
        assert len(ring["second"]) == 60, ring
        assert ring["second"][-1] > 0, ring

        assert node.shutdown() == 0, "unclean exit"
    finally:
        try:
            node.proc.kill()
        except OSError:
            pass
