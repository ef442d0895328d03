"""The readers of the CLIENT's half of a served call (ISSUE 28) on hand-made
`client_before` / `client_after` tables, as test_benchmark_stage_readers.py
makes the server's: a window is `after - before` of the client's own dumps
and nothing else; a client that sent no table reads None, never 0; the
server's table is never read in the client's place, nor the reverse.
Host-only: no chip, no build/, no client."""
import pytest

from benchmark import manifest, stages

MAN = manifest.load()
# reader: (stage of the client's table, the server-side reader of that
# stage's name, if the server has one)
CLIENT_STAGE_READERS = {
    "trpc_issue_mean_us": ("trpc.issue", None),
    "tici_reply_handoff_mean_us": ("tici.link_handoff",
                                   "tici_link_handoff_p50_us"),
    "tnet_client_cut_mean_us": ("tnet.consume_to_cut",
                                "tnet_consume_to_cut_mean_us"),
    "trpc_caller_wake_mean_us": ("trpc.caller_wake", None),
    "tici_reply_handoff_1m_mean_us": ("tici.link_handoff",
                                      "tici_link_handoff_p99_us"),
    "tnet_client_cut_1m_mean_us": ("tnet.consume_to_cut",
                                   "tnet_consume_to_cut_mean_us"),
}
COUNTER_READER = "tfiber_client_rescued_wakeups"
B100, B1000 = 52, 79  # PercentileHistogram::bucket_of(100), (1000)


def stage_dump(count, sum_us, buckets):
    return {"count": count, "sum_us": sum_us, "max_us": 5000,
            "buckets": [list(b) for b in buckets]}


def dump(stage_tables=None, counters=None):
    """One process's dump in a scrape's shape."""
    return {"status": {"stages": stage_tables or {}},
            "vars": counters or {}}


# Warm-up: 1000 slow samples. Window: 98 at ~100 us, 2 at ~1000 us.
WARM = stage_dump(1000, 5_000_000, [(B1000, 600), (120, 400)])
DONE = stage_dump(1100, 5_011_800, [(B100, 98), (B1000, 602), (120, 400)])
# Another process's table of the same stage: a window mean of 7 us.
OTHER_WARM = stage_dump(50, 1_000, [(20, 50)])
OTHER_DONE = stage_dump(150, 1_700, [(7, 100), (20, 50)])


def test_the_clients_readers_are_the_manifests():
    assert manifest.problems(MAN) == []
    by_name = {m["name"]: m for m in MAN["per_layer"]}
    for name in [*CLIENT_STAGE_READERS, COUNTER_READER]:
        entry, mod = by_name[name], manifest.reader(name)
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            entry["layer"], entry["unit"], entry["moves"], entry["source"])
        assert entry["better"] == "lower"
        # each lists only cells the served driver runs: the ring's process
        # has no client to dump a table
        for cell in entry["workloads"]:
            cfg = manifest.config(MAN, manifest.cell(MAN, cell))
            assert cfg["driver"] == "served"


@pytest.mark.parametrize("name", sorted(CLIENT_STAGE_READERS))
def test_client_stage_reader_reads_the_clients_window_only(name):
    stage, _ = CLIENT_STAGE_READERS[name]
    read = manifest.reader(name).read
    obs = {"client_before": dump({stage: WARM}),
           "client_after": dump({stage: DONE})}
    assert read(obs) == pytest.approx(118.0)
    # The server's table of the same stage beside it changes nothing.
    both = dict(obs, before=dump({stage: OTHER_WARM}),
                after=dump({stage: OTHER_DONE}))
    assert read(both) == pytest.approx(118.0)
    # An empty window, a table without the stage: nothing, never a 0.
    assert read({"client_before": dump({stage: DONE}),
                 "client_after": dump({stage: DONE})}) is None
    assert read({"client_before": dump({"other.stage": WARM}),
                 "client_after": dump({"other.stage": DONE})}) is None


@pytest.mark.parametrize("name", sorted(CLIENT_STAGE_READERS))
def test_a_client_that_sent_no_table_reads_none(name):
    stage, _ = CLIENT_STAGE_READERS[name]
    read = manifest.reader(name).read
    server_only = {"before": dump({stage: WARM}),
                   "after": dump({stage: DONE})}
    # a stale build/echo_load: the driver passes None for both dumps
    assert read(dict(server_only, client_before=None,
                     client_after=None)) is None
    # only one edge, a dump without a table, another driver's observation
    assert read(dict(server_only, client_after=dump({stage: DONE}))) is None
    assert read({"client_before": {"status": {}},
                 "client_after": {"status": {}}}) is None
    assert read({"t_first_op": 1.0, "window_s": 3.0}) is None
    assert read({}) is None
    # and the server's table is never read in the client's place
    assert read(server_only) is None


@pytest.mark.parametrize("name", sorted(
    n for n, (_, server) in CLIENT_STAGE_READERS.items() if server))
def test_the_servers_reader_never_reads_the_clients_table(name):
    stage, server_reader = CLIENT_STAGE_READERS[name]
    read = manifest.reader(server_reader).read
    client_only = {"client_before": dump({stage: WARM}),
                   "client_after": dump({stage: DONE})}
    assert read(client_only) is None
    both = dict(client_only, before=dump({stage: OTHER_WARM}),
                after=dump({stage: OTHER_DONE}))
    assert read(both) == pytest.approx(7.0)  # mean, p50 and p99 alike
    assert manifest.reader(name).read(both) == pytest.approx(118.0)


def test_client_rescued_wakeups_sums_the_clients_found_work_counters():
    read = manifest.reader(COUNTER_READER).read
    before = {"rpc_scheduler_park_timeouts": 40,
              "rpc_scheduler_park_timeouts_found_work": 1,
              "rpc_link_credit_wait_timeouts_found_work": 0}
    after = {"rpc_scheduler_park_timeouts": 90,
             "rpc_scheduler_park_timeouts_found_work": 3,
             "rpc_link_credit_wait_timeouts_found_work": 1,
             "rpc_socket_epollout_timeouts_found_work": 0}
    obs = {"client_before": dump(counters=before),
           "client_after": dump(counters=after)}
    assert read(obs) == 3.0
    assert read({"client_before": dump(counters=after),
                 "client_after": dump(counters=after)}) == 0.0
    # A client without the counters, or with no dump at all, reads nothing.
    assert read({"client_before": dump(counters={"x": 1}),
                 "client_after": dump(counters={"x": 2})}) is None
    assert read({"client_before": None, "client_after": None}) is None
    assert read({}) is None


def test_each_sides_counters_stay_on_their_side():
    client = manifest.reader(COUNTER_READER).read
    server = manifest.reader("tfiber_rescued_wakeups").read
    quiet = {"rpc_scheduler_park_timeouts_found_work": 5}
    stopped = {"rpc_scheduler_park_timeouts_found_work": 7}
    server_stop = {"before": dump(counters=quiet),
                   "after": dump(counters=stopped)}
    client_stop = {"client_before": dump(counters=quiet),
                   "client_after": dump(counters=stopped)}
    assert server(server_stop) == 2.0 and client(server_stop) is None
    assert client(client_stop) == 2.0 and server(client_stop) is None
    both = dict(server_stop, client_before=dump(counters=quiet),
                client_after=dump(counters=quiet))
    assert server(both) == 2.0 and client(both) == 0.0


def test_the_stage_arithmetic_takes_a_side():
    obs = {"before": dump({"s": OTHER_WARM}), "after": dump({"s": OTHER_DONE}),
           "client_before": dump({"s": WARM}),
           "client_after": dump({"s": DONE})}
    assert stages.mean_us(obs, "s") == stages.mean_us(obs, "s", "server")
    assert stages.mean_us(obs, "s", "server") == pytest.approx(7.0)
    assert stages.mean_us(obs, "s", "client") == pytest.approx(118.0)
    assert stages.window(obs, "s", "client")["count"] == 100
    assert stages.quantile_us(obs, "s", 0.5, "client") == 100.0
    assert stages.quantile_us(obs, "s", 0.99, "client") == 992.0
    assert stages.quantile_us(obs, "s", 0.99, "server") == 7.0
    with pytest.raises(KeyError):
        stages.window(obs, "s", "proxy")
