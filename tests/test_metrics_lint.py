"""Prometheus exposition lint (ISSUE 4 satellite): boots one server,
scrapes /metrics, and checks the text-format contract in pure Python
(promtool-style):

  * metric names match [a-zA-Z_:][a-zA-Z0-9_:]*;
  * every sample's family has a preceding # TYPE line (with _sum/_count
    resolving to their summary stem), and no family declares TYPE twice;
  * summaries are well-formed: quantile-labelled samples plus _sum and
    _count, quantile values non-decreasing within a label set.

Also asserts the /vars?series= ring endpoint returns the fixed 60-point
per-second shape (the fake-clock rollover proof lives in the C++ suite).
"""
import json
import re
import time

from test_chaos_soak import Node, _free_ports, _http_get

NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})?\s+"
    r"(-?[0-9.eE+-]+|NaN|[+-]Inf)$")
LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _lint_exposition(text):
    """Returns (families, errors): families maps name -> type."""
    families = {}
    errors = []
    samples = []
    for i, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split()
            if len(parts) >= 2 and parts[1] == "TYPE":
                if len(parts) != 4:
                    errors.append("line %d: malformed TYPE: %r" % (i, line))
                    continue
                name, mtype = parts[2], parts[3]
                if not NAME_RE.match(name):
                    errors.append("line %d: bad family name %r" % (i, name))
                if mtype not in ("gauge", "counter", "summary",
                                 "histogram", "untyped"):
                    errors.append("line %d: bad type %r" % (i, mtype))
                if name in families:
                    errors.append("line %d: duplicate TYPE for %r"
                                  % (i, name))
                families[name] = mtype
            continue
        m = SAMPLE_RE.match(line)
        if not m:
            errors.append("line %d: malformed sample: %r" % (i, line))
            continue
        name, labels = m.group(1), m.group(3) or ""
        if not NAME_RE.match(name):
            errors.append("line %d: bad metric name %r" % (i, name))
        # TYPE must precede the sample, resolving summary suffixes.
        family = name
        if family not in families:
            for suffix, types in (("_sum", ("summary", "histogram")),
                                  ("_count", ("summary", "histogram")),
                                  ("_bucket", ("histogram",))):
                stem = name[: -len(suffix)] if name.endswith(suffix) else None
                if stem and families.get(stem) in types:
                    family = stem
                    break
        if family not in families:
            errors.append("line %d: sample %r has no preceding TYPE"
                          % (i, name))
        samples.append((name, dict(LABEL_RE.findall(labels)),
                        m.group(4), i))
    # Summary shape: quantiles non-decreasing per label set, _sum/_count
    # present.
    for fam, mtype in families.items():
        if mtype != "summary":
            continue
        groups = {}
        has_sum = has_count = False
        for name, labels, value, i in samples:
            if name == fam + "_sum":
                has_sum = True
            if name == fam + "_count":
                has_count = True
            if name == fam and "quantile" in labels:
                key = tuple(sorted(
                    (k, v) for k, v in labels.items() if k != "quantile"))
                groups.setdefault(key, []).append(
                    (float(labels["quantile"]), float(value), i))
        if not has_sum or not has_count:
            errors.append("summary %r missing _sum/_count" % fam)
        if not groups:
            errors.append("summary %r has no quantile samples" % fam)
        for key, qs in groups.items():
            qs.sort()
            vals = [v for _, v, _ in qs]
            if any(b < a for a, b in zip(vals, vals[1:])):
                errors.append("summary %r quantiles not monotone: %r"
                              % (fam, qs))
    # Histogram shape, per label set: cumulative buckets ending in +Inf,
    # which equals _count; _sum present.
    for fam, mtype in families.items():
        if mtype != "histogram":
            continue
        groups = {}
        for name, labels, value, i in samples:
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"))
            g = groups.setdefault(key, {"buckets": [], "sum": None,
                                        "count": None})
            if name == fam + "_bucket" and "le" in labels:
                g["buckets"].append((float(labels["le"].replace(
                    "+Inf", "inf")), float(value)))
            elif name == fam + "_sum":
                g["sum"] = float(value)
            elif name == fam + "_count":
                g["count"] = float(value)
        groups = {k: g for k, g in groups.items()
                  if g["buckets"] or g["sum"] is not None
                  or g["count"] is not None}
        if not groups:
            errors.append("histogram %r has no samples" % fam)
        for key, g in groups.items():
            g["buckets"].sort()
            vals = [v for _, v in g["buckets"]]
            if not g["buckets"] or g["buckets"][-1][0] != float("inf"):
                errors.append("histogram %r %r has no +Inf bucket"
                              % (fam, key))
            elif g["count"] is None or g["sum"] is None:
                errors.append("histogram %r %r missing _sum/_count"
                              % (fam, key))
            elif vals[-1] != g["count"]:
                errors.append("histogram %r %r: +Inf bucket %r != _count "
                              "%r" % (fam, key, vals[-1], g["count"]))
            if any(b < a for a, b in zip(vals, vals[1:])):
                errors.append("histogram %r %r buckets not cumulative"
                              % (fam, key))
    return families, errors


def test_metrics_exposition_lint(cpp_build, tmp_path):
    binary = cpp_build / "mesh_node"
    assert binary.exists(), "mesh_node not built"
    (port,) = _free_ports(1)
    peers_file = tmp_path / "peers"
    peers_file.write_text("127.0.0.1:%d\n" % port)
    # QoS on (ISSUE 8): the node's self-echo traffic then populates the
    # per-tenant labelled families for the lint below.
    from test_chaos_soak import NODE_FLAGS
    node = Node(binary, port, 0, peers_file,
                flags=NODE_FLAGS + ["rpc_qos_enabled=true"])
    try:
        assert node.wait_ready(), "node never became ready"
        # Let traffic + the 1Hz series sampler produce real data.
        time.sleep(2.5)

        text = _http_get(port, "/metrics")
        families, errors = _lint_exposition(text)
        assert not errors, "exposition lint failed:\n" + "\n".join(errors)
        # The method LatencyRecorder must export a REAL summary family
        # now, not flat _field gauges parsed out of JSON.
        assert families.get("benchpb_EchoService_Echo") == "summary", \
            sorted(families)
        assert "benchpb_EchoService_Echo_p50" not in families
        # Flag->var bridge: flags are scrape-able alongside metrics.
        assert families.get("flag_enable_rpcz") == "gauge", sorted(families)
        assert re.search(r"^flag_enable_rpcz [01]$", text, re.M), text[:500]
        # ISSUE 6 attribution families: dispatcher/scheduler counters as
        # labelled gauges, distributions as labelled summaries, and the
        # socket write-batch summary — all must pass the same lint.
        assert families.get("rpc_dispatcher_epoll_waits") == "gauge", \
            sorted(families)
        assert families.get("rpc_dispatcher_events") == "gauge"
        assert families.get("rpc_dispatcher_events_per_wake") == "summary"
        assert families.get("rpc_dispatcher_wake_to_dispatch_us") == \
            "summary"
        assert families.get("rpc_scheduler_steals") == "gauge"
        assert families.get("rpc_scheduler_remote_overflows") == "gauge"
        assert families.get("rpc_scheduler_urgent_handoffs") == "gauge"
        assert families.get("rpc_scheduler_runqueue_highwater") == "gauge"
        assert families.get("rpc_socket_write_batch_bytes") == "summary"
        # ISSUE 25 stage clock: one real histogram family, a series a
        # stage, and the safety-net counters as plain gauges.
        assert families.get("rpc_stage_us") == "histogram", sorted(families)
        assert re.search(
            r'^rpc_stage_us_count\{stage="tfiber.wake_to_run"\} [1-9]\d*$',
            text, re.M), text[:500]
        assert families.get("rpc_scheduler_park_timeouts") == "gauge"
        assert families.get(
            "rpc_scheduler_park_timeouts_found_work") == "gauge"
        assert re.search(
            r'^rpc_dispatcher_epoll_waits\{loop="0"\} \d+$', text, re.M), \
            text[:500]
        assert re.search(
            r'^rpc_scheduler_steals\{pool="0"\} \d+$', text, re.M)
        # ISSUE 8 multi-tenant families: per-tenant counters as labelled
        # gauges, the served-latency distribution as a labelled summary —
        # same lint, same per-tuple series rings.
        assert families.get("rpc_tenant_admitted") == "gauge", \
            sorted(families)
        assert families.get("rpc_tenant_shed") == "gauge"
        assert families.get("rpc_tenant_queued") == "gauge"
        assert families.get("rpc_tenant_latency_us") == "summary"
        assert re.search(
            r'^rpc_tenant_admitted\{tenant="default"\} \d+$', text, re.M), \
            text[:500]
        # ISSUE 15 work-priced admission families: per-tenant estimated
        # milli-cost counters, the measured per-request cost summary,
        # the gradient concurrency-limit gauge, the process-wide cost
        # totals, and the fair-queue sojourn summary — all present on a
        # qos-enabled node from its own self-echo traffic.
        assert families.get("rpc_tenant_cost_admitted") == "gauge", \
            sorted(families)
        assert families.get("rpc_tenant_cost_shed") == "gauge"
        assert families.get("rpc_tenant_cost_units") == "summary"
        assert families.get("rpc_tenant_gradient_limit") == "gauge"
        assert families.get("rpc_server_cost_admitted") == "gauge"
        assert families.get("rpc_server_cost_shed") == "gauge"
        assert families.get("rpc_server_queue_delay_us") == "summary"
        assert re.search(
            r'^rpc_tenant_cost_admitted\{tenant="default"\} \d+$', text,
            re.M), text[:500]
        # The gradient limit is a LIVE positive limit (converging from
        # the node's own traffic), not a placeholder zero.
        m = re.search(
            r'^rpc_tenant_gradient_limit\{tenant="default"\} (\d+)$',
            text, re.M)
        assert m is not None and int(m.group(1)) > 0, m
        # ISSUE 10 zero-copy crash-safety families: the pinned-block
        # lease ledger (live gauge + reclamation counters) and the
        # epoch fence — present (0-valued) even before the first pin.
        assert families.get("rpc_pool_pinned_blocks") == "gauge", \
            sorted(families)
        assert families.get("rpc_pool_lease_expired") == "gauge"
        assert families.get("rpc_pool_reaped") == "gauge"
        assert families.get("rpc_pool_peer_released") == "gauge"
        assert families.get("rpc_pool_epoch_rejects") == "gauge"
        assert re.search(r"^rpc_pool_pinned_blocks \d+$", text, re.M), \
            text[:500]
        # ISSUE 12 response-direction descriptor families: present
        # (0-valued) from the first scrape, same lint as everything else.
        for fam in ("rpc_pool_desc_rsp_sends",
                    "rpc_pool_desc_rsp_send_bytes",
                    "rpc_pool_desc_rsp_fallbacks",
                    "rpc_pool_desc_rsp_resolves",
                    "rpc_pool_desc_rsp_resolve_bytes",
                    "rpc_pool_desc_rsp_rejects",
                    "rpc_pool_desc_rsp_acks"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
        # ISSUE 13 collective families: counters present (0-valued)
        # before any round, plus the per-algorithm bus-bandwidth family
        # with one series per algorithm.
        for fam in ("rpc_collective_ops", "rpc_collective_steps",
                    "rpc_collective_retries", "rpc_collective_reforms",
                    "rpc_collective_bytes",
                    "rpc_collective_desc_fallbacks"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
        assert families.get("rpc_collective_busbw_mbps") == "gauge"
        # hier_allreduce: the ISSUE 14 hierarchical (zone ring -> leader
        # exchange over dcn -> broadcast) series, 0-valued before the
        # first cross-pod round.
        for alg in ("allreduce", "allgather", "alltoall",
                    "allreduce_serial", "hier_allreduce"):
            assert re.search(
                r'^rpc_collective_busbw_mbps\{alg="%s"\} \d+$' % alg,
                text, re.M), alg
        # ISSUE 18 one-sided verb families: the verb plane's counters
        # (posted/completed verbs, bytes moved, stale-epoch rejects, CQ
        # parks) and the collective verbs-lane step/fallback counters —
        # all present (0-valued, eagerly exposed) before the first post.
        for fam in ("rpc_verbs_posted", "rpc_verbs_completed",
                    "rpc_verbs_bytes", "rpc_verbs_stale_rejects",
                    "rpc_verbs_cq_parks", "rpc_collective_verb_steps",
                    "rpc_collective_verb_fallbacks"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
            assert re.search(r"^%s \d+$" % fam, text, re.M), fam
        # ISSUE 19 flight-recorder families: exposed from the first
        # scrape (the recorder is always-on, so events may already be
        # non-zero from the node's own traffic; dump_count must still be
        # 0 — nothing crashed).
        for fam in ("rpc_blackbox_events", "rpc_blackbox_dropped",
                    "rpc_blackbox_ring_highwater", "rpc_flight_dump_count"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
            assert re.search(r"^%s \d+$" % fam, text, re.M), fam
        assert re.search(r"^rpc_flight_dump_count 0$", text, re.M), \
            "a dump happened on a healthy node"
        # /blackbox renders in both forms; the json is the exact document
        # tools/blackbox_merge.py consumes for live nodes.
        bb = json.loads(_http_get(port, "/blackbox?format=json"))
        for key in ("node", "pid", "wall_us", "ticks_per_us", "rings"):
            assert key in bb, (key, sorted(bb))
        assert isinstance(bb["rings"], list) and bb["rings"], bb
        assert any(r["events"] for r in bb["rings"]), \
            "always-on recorder captured nothing"
        assert "flight recorder:" in _http_get(port, "/blackbox")
        # Satellite: the contention profiler page grew a machine form
        # with the same fresh-window semantics as the text view.
        cont = json.loads(
            _http_get(port, "/hotspots/contention?format=json"))
        for key in ("total_count", "total_wait_us", "other_count",
                    "sites"):
            assert key in cont, (key, sorted(cont))
        assert isinstance(cont["sites"], list), cont
        for site in cont["sites"]:
            assert set(site) == {"site", "count", "wait_us"}, site
        assert "fiber-mutex contention" in _http_get(
            port, "/hotspots/contention")
        # ISSUE 12/14 transport-tier attribution: labelled families with
        # one series per registered endpoint type, now including the
        # cross-pod dcn tier.
        for fam in ("rpc_transport_in_bytes", "rpc_transport_out_bytes",
                    "rpc_transport_desc_in_bytes",
                    "rpc_transport_desc_out_bytes",
                    "rpc_transport_credit_stalls", "rpc_transport_ops"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
        for tier in ("tcp", "ici", "shm_xproc", "device", "dcn"):
            assert re.search(
                r'^rpc_transport_out_bytes\{transport="%s"\} \d+$' % tier,
                text, re.M), tier
        # ISSUE 17 resumable push-stream families: every counter present
        # (0-valued, eagerly exposed) before the first stream, plus the
        # time-to-first-token summary — and /streams renders in both
        # forms with the counters the restart soak scrapes.
        for fam in ("rpc_stream_open", "rpc_stream_resumed",
                    "rpc_stream_replayed_chunks",
                    "rpc_stream_credit_stalls", "rpc_stream_aborts"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
            assert re.search(r"^%s \d+$" % fam, text, re.M), fam
        assert families.get("rpc_stream_ttft_us") == "summary", \
            sorted(families)
        # ISSUE 18 satellite: push-stream chunks are descriptor-eligible
        # on capable links — sends/fallbacks/resolves/rejects counted,
        # present 0-valued from the first scrape.
        for fam in ("rpc_stream_desc_chunks", "rpc_stream_desc_fallbacks",
                    "rpc_stream_desc_resolves", "rpc_stream_desc_rejects"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
            assert re.search(r"^%s \d+$" % fam, text, re.M), fam
        streams = json.loads(_http_get(port, "/streams?format=json"))
        for key in ("open", "resumed", "replayed_chunks",
                    "credit_stalls", "aborts", "ring_highwater"):
            assert key in streams, (key, streams)
        assert isinstance(streams.get("server_streams"), list), streams
        assert "push streams" in _http_get(port, "/streams")
        # ISSUE 14 locality-zone LB: spill accounting present (0-valued)
        # before any cross-zone member exists.
        assert families.get("rpc_lb_zone_spills") == "gauge", \
            sorted(families)
        assert families.get("rpc_lb_zone_local_picks") == "gauge"
        assert re.search(r"^rpc_lb_zone_spills \d+$", text, re.M)
        # ISSUE 20 outlier-ejection families: present (0-valued, eagerly
        # exposed) from the first scrape of a healthy node — and the live
        # ejected-now gauge must actually be zero, nothing on a healthy
        # single-node mesh qualifies for ejection.
        for fam in ("rpc_outlier_ejections", "rpc_outlier_reinstatements",
                    "rpc_outlier_probe_passes", "rpc_outlier_probe_fails",
                    "rpc_outlier_eject_vetoes", "rpc_outlier_ejected_now"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
            assert re.search(r"^%s \d+$" % fam, text, re.M), fam
        assert re.search(r"^rpc_outlier_ejected_now 0$", text, re.M), \
            "a healthy mesh ejected someone"
        # /outliers renders in both forms; every mesh_node runs at least
        # the naming-service LB channel, so one tracker is always live
        # and its (self) backend reports healthy.
        outl = json.loads(_http_get(port, "/outliers?format=json"))
        for key in ("trackers", "ejections", "reinstatements",
                    "ejected_now", "probe_passes", "probe_fails",
                    "eject_vetoes"):
            assert key in outl, (key, sorted(outl))
        assert isinstance(outl["trackers"], list) and outl["trackers"], \
            outl
        tr = outl["trackers"][0]
        assert isinstance(tr.get("backends"), list) and tr["backends"], tr
        assert tr["backends"][0]["state"] == "HEALTHY", tr
        assert "tracker " in _http_get(port, "/outliers")
        # /pools json carries the lease direction column + tier table
        # (dcn: descriptor-INCAPABLE cross-process byte stream).
        pools = json.loads(_http_get(port, "/pools?format=json"))
        assert isinstance(pools.get("leases"), list), pools
        tiers = {t["name"]: t for t in pools.get("transports", [])}
        assert set(tiers) >= {"tcp", "ici", "shm_xproc", "device",
                              "dcn"}, tiers
        assert tiers["tcp"]["descriptor_capable"] == 0
        assert tiers["ici"]["descriptor_capable"] == 1
        assert tiers["shm_xproc"]["cross_process"] == 1
        assert tiers["dcn"]["descriptor_capable"] == 0
        assert tiers["dcn"]["cross_process"] == 1
        # ISSUE 18 capability bits: shm-ICI tiers take one-sided verbs
        # with a real SGL budget; byte-stream tiers do not (their posts
        # run the emulated two-sided wire path).
        assert tiers["ici"]["one_sided"] == 1, tiers
        assert tiers["ici"]["sgl_max"] >= 4, tiers
        assert tiers["shm_xproc"]["one_sided"] == 1, tiers
        assert tiers["tcp"]["one_sided"] == 0, tiers
        assert tiers["dcn"]["one_sided"] == 0, tiers

        # /vars?series= returns the fixed 60/60/24-point ring shape.
        # Poll: on a loaded host the 1Hz sampler may lag a little before
        # the ring tail shows a non-zero uptime.
        deadline = time.time() + 20.0
        while True:
            ring = json.loads(
                _http_get(port, "/vars?series=process_uptime_seconds"))
            if ring["ticks"] >= 2 and ring["second"][-1] >= 1:
                break
            assert time.time() < deadline, ring
            time.sleep(0.5)
        assert len(ring["second"]) == 60, ring
        assert len(ring["minute"]) == 60
        assert len(ring["hour"]) == 24
        # Labelled families feed per-tuple rings (ISSUE 6): the loop-0
        # dispatcher counter has its own series.
        disp_ring = json.loads(
            _http_get(port, "/vars?series=rpc_dispatcher_epoll_waits_loop_0"))
        assert len(disp_ring["second"]) == 60, disp_ring
        # Unknown series 404s with guidance instead of a silent empty.
        try:
            _http_get(port, "/vars?series=no_such_series_name")
            assert False, "expected 404"
        except Exception:
            pass

        assert node.shutdown() == 0, "unclean exit"
    finally:
        try:
            node.proc.kill()
        except OSError:
            pass


def test_router_metrics_lint(cpp_build, tmp_path):
    """ISSUE 16: a live tpu_router node passes the same exposition lint
    and publishes every rpc_router_* family 0-valued from the very
    first scrape — dashboards never see a family pop into existence."""
    import subprocess

    mesh_bin = cpp_build / "mesh_node"
    router_bin = cpp_build / "tpu_router"
    assert router_bin.exists(), "tpu_router not built"
    backend_port, router_port = _free_ports(2)
    backends_file = tmp_path / "backends"
    backends_file.write_text("127.0.0.1:%d\n" % backend_port)
    backend = Node(mesh_bin, backend_port, 0, backends_file,
                   extra_args=("--lb_only", "--traffic_delay_ms",
                               "600000"))
    router = None
    try:
        assert backend.wait_ready(), "backend never became ready"
        router = subprocess.Popen(
            [str(router_bin), "--port", str(router_port),
             "--backends", str(backends_file)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL)
        # READY handshake (same stdout contract as mesh_node).
        deadline = time.time() + 30.0
        line = b""
        while not line.startswith(b"READY"):
            assert time.time() < deadline, "router never became ready"
            line = router.stdout.readline()

        text = _http_get(router_port, "/metrics")
        families, errors = _lint_exposition(text)
        assert not errors, "router lint failed:\n" + "\n".join(errors)
        for fam in ("rpc_router_forwards", "rpc_router_forward_failures",
                    "rpc_router_hedges", "rpc_router_hedge_wins",
                    "rpc_router_reroutes", "rpc_router_session_repins",
                    "rpc_router_edge_sheds",
                    "rpc_router_hedge_refreshes"):
            assert families.get(fam) == "gauge", (fam, sorted(families))
            assert re.search(r"^%s \d+$" % fam, text, re.M), fam
        # The backend-latency recorder exports a real summary family.
        assert families.get("rpc_router_backend_latency") == "summary", \
            sorted(families)
        # /router renders in both forms and the json has the shape the
        # restart soak polls.
        state = json.loads(
            _http_get(router_port, "/router?format=json"))
        assert isinstance(state["backends"], list) and state["backends"]
        assert "sessions" in state and "hedges" in state, state
        assert "router state" in _http_get(router_port, "/router")
    finally:
        try:
            backend.proc.kill()
        except OSError:
            pass
        if router is not None:
            try:
                router.kill()
            except OSError:
                pass
