"""kvpb.Cache served from this process (ISSUE 33): the pull server's second
service, the attachment staged from an offset, and `brpc_tpu.kv_service`
over them, held to the plain reference exactly (integers and bytes: limit
0). CPU backend, a small pool (3 layers, 2 session slots, 64 KiB a layer,
16 KiB chunks) -- but where a Get's reply parts lie, which takes 9 MiB
layers in a process of its own; every test has a time limit of its own, so
a hang fails here and stalls nothing."""
import signal
import threading
import urllib.request

import numpy as np
import pytest

from brpc_tpu import kv_reference

LAYERS, SLOTS, LAYER_BYTES, CHUNK = 3, 2, 65536, 16384
LIMIT_S = 90


@pytest.fixture(autouse=True)
def time_limit():
    def expired(signum, frame):
        raise TimeoutError(f"the test ran over {LIMIT_S} s: a thread hangs")

    before = signal.signal(signal.SIGALRM, expired)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


@pytest.fixture
def native(cpp_build):
    from brpc_tpu import native

    return native


def serve(chunk_bytes=CHUNK, **kw):
    import jax

    from brpc_tpu import kv_service

    sizes = dict(layers=LAYERS, sessions=SLOTS, layer_bytes=LAYER_BYTES,
                 chunk_bytes=chunk_bytes)
    return kv_service.serve(jax.devices("cpu")[0], **{**sizes, **kw})


@pytest.fixture
def service(native):
    svc = serve()
    yield svc
    svc.close()


@pytest.fixture
def channel(native, service):
    ch = native.StepChannel(service.port, ici=True)
    yield ch
    ch.close()


@pytest.fixture
def pull_server(native):
    server = native.PullServer()
    yield server
    server.stop()


def layer_bytes(seed, nbytes=LAYER_BYTES):
    return np.random.default_rng(seed).integers(0, 256, nbytes,
                                                dtype=np.uint8)


def span_names(until):
    """The names of the spans kept, once `until` is among them: a span is
    recorded as it ends, which for kv.reply is after the caller has its
    answer."""
    import time

    from brpc_tpu import spans

    deadline = time.monotonic() + 5
    while True:
        names = [rec[0] for rec in spans.snapshot()]
        if until in names or time.monotonic() > deadline:
            return names
        time.sleep(0.01)


def counters(port):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/vars",
                                timeout=10) as r:
        return {ln.split(" : ")[0]: int(ln.split(" : ")[1].split()[0])
                for ln in r.read().decode().splitlines()
                if ln.startswith("rpc_kv_")}


# ------------------------------------------------------------ the reference

def test_reference_word_by_hand():
    x = np.array([7, 9, 1, 2], dtype="<u4")
    assert kv_reference.word(x.tobytes()) == 7 * 1 + 9 * 3 + 1 * 5 + 2 * 7
    big = np.full(6, 0xFFFFFFFF, dtype="<u4")
    assert kv_reference.word(big.tobytes()) == sum(
        0xFFFFFFFF * (2 * j + 1) for j in range(6)) & 0xFFFFFFFF


def test_reference_evicts_the_oldest_whole_session_only_when_full():
    ref = kv_reference.Cache(2, 3, 16)
    assert [ref.put(s, 0, bytes(16))[1] for s in (5, 6, 5, 6)] == [0, 1, 0, 1]
    ref.put(5, 1, b"\x01" * 16)
    assert ref.slots == {5: (0, 0), 6: (1, 1)}
    assert ref.put(7, 2, bytes(16)) == (0, 2)  # evicts 5, takes its slot
    assert ref.slots == {6: (1, 1), 7: (0, 2)}
    for layer in (0, 1):
        with pytest.raises(kv_reference.NotFound):
            ref.get(5, layer)
    with pytest.raises(kv_reference.NotFound):
        ref.get(7, 0)  # a layer never put since the slot was given
    assert ref.get(6, 0) == bytes(16) and ref.get(7, 2) == bytes(16)
    assert ref.put(5, 0, bytes(16))[1] == 3  # back as a new session


@pytest.mark.parametrize("nbytes, layer", [(0, 0), (12, 0), (24, 0), (16, 3),
                                           (16, -1)])
def test_reference_refuses_what_the_service_refuses(nbytes, layer):
    with pytest.raises(ValueError):
        kv_reference.Cache(2, 3, 16).put(1, layer, bytes(nbytes))


def test_reference_imports_nothing_of_the_served_path():
    import ast
    import inspect

    names = set()
    for node in ast.walk(ast.parse(inspect.getsource(kv_reference))):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
    assert names == {"numpy"}


# ------------------------------------------- the pull server and the client

def test_take_says_which_method_and_the_requests_fields(native, pull_server):
    """One queue behind both services: a Step, a Put and a Get come out in
    the order they arrived, each with its method, and a Put's or Get's
    session and layer."""
    out = {}

    def call(name, fn):
        ch = native.StepChannel(pull_server.port, ici=True)
        try:
            out[name] = fn(ch)
        except native.RpcError as e:
            out[name] = e.code
        finally:
            ch.close()

    x = layer_bytes(1, 4096)
    threads = []
    taken = []
    for name, fn in (("step", lambda ch: ch.call(x[:64]).tobytes()),
                     ("put", lambda ch: ch.put(2**63 + 5, 60, x)),
                     ("get", lambda ch: ch.get(77, 3, 64).tobytes())):
        threads.append(threading.Thread(target=call, args=(name, fn)))
        threads[-1].start()
        taken.append(pull_server.take(10_000_000))
    step, put, get = taken
    assert [c.method for c in taken] == [native.STEP, native.PUT, native.GET]
    assert (step.nbytes, step.session, step.layer) == (64, 0, 0)
    assert (put.nbytes, put.session, put.layer) == (4096, 2**63 + 5, 60)
    assert (get.nbytes, get.session, get.layer) == (0, 77, 3)
    with pytest.raises(ValueError):
        step.reply_put(1, 2)  # a Put's answer answers a Put
    step.reply(np.arange(8, dtype=np.uint8))
    put.reply_put(0xDEADBEEF, 2**40 + 1)
    get.fail(native.KV_NOT_FOUND, "no such layer")
    for t in threads:
        t.join()
    assert out == {"step": bytes(range(8)), "put": (0xDEADBEEF, 2**40 + 1),
                   "get": native.KV_NOT_FOUND}
    with pytest.raises(ValueError):
        put.reply_put(1, 2)  # answered once, for good


def test_a_reply_write_outside_one_part_is_refused_and_writes_nothing(
        native, pull_server):
    """A reply of 40,000 bytes in parts of 16 KiB (the last one 7,232): a
    write that crosses into the next part, one that runs past the end of the
    short last part, and one past the last part are refused; the writes
    that fit answer with exactly their bytes."""
    got = []
    caller = threading.Thread(target=lambda: got.append(native.StepChannel(
        pull_server.port, ici=True).get(1, 0, 40000).tobytes()))
    caller.start()
    call = pull_server.take(10_000_000)
    call.reply_begin(40000, 16384)
    x = layer_bytes(9, 40000)
    for offset, n in ((16000, 1000), (32768 + 7000, 300), (32768 + 8000, 8),
                      (49152, 8)):
        with pytest.raises(ValueError):
            call.reply_write(offset, x[:n])
    for at in (0, 16384, 32768):
        call.reply_write(at, x[at:at + 16384])
    call.reply_commit()
    caller.join(30)
    assert not caller.is_alive() and got == [x.tobytes()]


@pytest.mark.parametrize("offset, cap", [
    (0, 40000), (8192, 8192), (8000, 9000), (16384 + 24, 16384),
    (39000, 4096), (40000, 64), (0, 48000)],
    ids=["whole", "one_block", "straddles", "mid_block", "zero_tail",
         "all_zeros", "whole_and_tail"])
def test_copy_into_from_an_offset_is_one_pass_with_the_crc(native,
                                                           pull_server,
                                                           offset, cap):
    """ISSUE 33: a chunk of the attachment goes into the slot by one walk
    that starts at the chunk's offset (the 40,000 bytes arrive over the shm
    link in several blocks), with the zero tail and the crc32c of all of
    the view, and counts as staged in one pass."""
    x = layer_bytes(offset + cap, 40000)
    got = []
    caller = threading.Thread(target=lambda: got.append(
        native.StepChannel(pull_server.port, ici=True).put(1, 0, x)))
    caller.start()
    call = pull_server.take(10_000_000)
    before = native.staging_counters()
    room = np.full(cap + 8, 0xAA, dtype=np.uint8)
    crc = call.copy_into(room[:cap], offset)
    call.reply_put(0, 0)
    caller.join()
    want = x[offset:offset + cap].tobytes()
    want += bytes(cap - len(want))
    assert room[:cap].tobytes() == want and room[cap:].tobytes() == b"\xaa" * 8
    assert crc == native.crc32c(want)
    after = native.staging_counters()
    assert (after["rpc_stage_fused_bytes"] - before["rpc_stage_fused_bytes"]
            == cap)


# ----------------------------------------------------------- the service

def test_every_put_and_get_equals_the_reference_through_an_eviction(
        native, service, channel):
    """Three sessions into two slots: every word and admission number is the
    reference's, every layer reads back byte for byte, the third session
    evicts the first whole, and a Get of it is the not-found error."""
    ref = kv_reference.Cache(SLOTS, LAYERS, LAYER_BYTES)
    for session in (11, 22, 33):
        for layer in range(LAYERS):
            x = layer_bytes(session + layer)
            assert channel.put(session, layer, x) == ref.put(session, layer,
                                                             x)
            assert (channel.get(session, layer, LAYER_BYTES).tobytes()
                    == ref.get(session, layer))
    assert {s: (t.slot, t.admitted) for s, t in service.table.items()} == (
        ref.slots)
    for layer in range(LAYERS):
        with pytest.raises(native.RpcError) as e:
            channel.get(11, layer, LAYER_BYTES)
        assert e.value.code == native.KV_NOT_FOUND
        with pytest.raises(kv_reference.NotFound):
            ref.get(11, layer)
    for session in (22, 33):  # the survivors, after the eviction
        for layer in range(LAYERS):
            assert (channel.get(session, layer, LAYER_BYTES).tobytes()
                    == ref.get(session, layer))
    assert service.failure is None


@pytest.mark.parametrize("chunk_bytes, chunks", [(65536, 1), (24576, 3),
                                                 (16384, 4)])
def test_the_word_does_not_depend_on_how_the_call_was_cut(native,
                                                          chunk_bytes,
                                                          chunks):
    """The same 64 KiB layer through 1, 3 and 4 chunks (24 KiB: a length
    that is not a multiple of the chunk) gives the reference's word and
    reads back whole."""
    from brpc_tpu import spans

    x = layer_bytes(99)
    svc = serve(chunk_bytes)
    channel = native.StepChannel(svc.port, ici=True)
    try:
        spans.clear()
        assert channel.put(5, 1, x) == (kv_reference.word(x), 0)
        assert channel.get(5, 1, LAYER_BYTES).tobytes() == x.tobytes()
        names = [rec[0] for rec in spans.snapshot()]
        assert names.count("kv.fill") == names.count("ring.launch") == chunks
        assert names.count("kv.join") == names.count("kv.reply") == 1
    finally:
        channel.close()
        svc.close()
    assert svc.failure is None


def test_the_default_chunk_is_the_largest_whole_mib_the_4mib_slot_holds():
    """ISSUE 34: a slot is the chunk plus 1 KiB of frame headroom
    (brpc_tpu/lane_service.py:48) and the largest slab class is 4 MiB
    (cpp/tici/block_pool.cc:323): 3 MiB fits it, 4 MiB does not, and a
    9 MiB layer of the configuration is 3 of them with nothing padded."""
    from brpc_tpu.kv_service import CHUNK_BYTES

    assert CHUNK_BYTES % (1 << 20) == 0
    assert CHUNK_BYTES + 1024 <= 4 << 20 < CHUNK_BYTES + (1 << 20) + 1024
    assert (9 << 20) % CHUNK_BYTES == 0


def test_a_9mib_layer_crosses_the_lane_in_three_chunks_at_the_default_cut(
        native):
    """The configuration's own layer size (kv_handoff_1chip: 9 MiB) and
    the program's default cut: 3 chunks a Put, a pool row with no padding,
    the reference's word, the layer read back byte for byte."""
    import jax

    from brpc_tpu import kv_service, spans

    nbytes = 9 << 20
    svc = kv_service.serve(jax.devices("cpu")[0], layers=1, sessions=1,
                           layer_bytes=nbytes)
    channel = native.StepChannel(svc.port, ici=True)
    try:
        assert svc.row_chunks == 3 and svc.pool_bytes == nbytes
        x = layer_bytes(34, nbytes)
        before = counters(svc.port)
        spans.clear()
        assert channel.put(7, 0, x) == (kv_reference.word(x), 0)
        assert channel.get(7, 0, nbytes).tobytes() == x.tobytes()
        names = span_names("kv.reply")
        assert names.count("kv.fill") == names.count("ring.launch") == 3
        after = counters(svc.port)
        assert after["rpc_kv_chunks"] - before["rpc_kv_chunks"] == 3
        assert (after["rpc_kv_bytes_landed"]
                - before["rpc_kv_bytes_landed"]) == nbytes
    finally:
        channel.close()
        svc.close()
    assert svc.failure is None


@pytest.mark.parametrize("nbytes", [8, 16384, 16392, 40000, 65528])
def test_a_put_shorter_than_the_layer_reads_back_at_its_own_length(
        native, service, channel, nbytes):
    x = layer_bytes(nbytes, nbytes)
    assert channel.put(3, 2, x) == (kv_reference.word(x), 0)
    assert channel.get(3, 2, LAYER_BYTES).tobytes() == x.tobytes()
    # Put again, shorter: the newest put is what the pool holds.
    y = layer_bytes(nbytes + 1, 8)
    assert channel.put(3, 2, y) == (kv_reference.word(y), 0)
    assert channel.get(3, 2, LAYER_BYTES).tobytes() == y.tobytes()


def test_a_layer_never_put_in_this_tenancy_is_not_found(native, service,
                                                        channel):
    """A session that takes an evicted one's slot never reads the bytes
    that were there."""
    for session in (1, 2):
        for layer in range(LAYERS):
            channel.put(session, layer, layer_bytes(session * 10 + layer))
    channel.put(3, 0, layer_bytes(30))  # evicts 1, takes slot 0
    assert service.table[3].slot == 0
    for layer in (1, 2):
        with pytest.raises(native.RpcError) as e:
            channel.get(3, layer, LAYER_BYTES)
        assert e.value.code == native.KV_NOT_FOUND
    with pytest.raises(native.RpcError) as e:
        channel.get(404, 0, LAYER_BYTES)  # never put at all
    assert e.value.code == native.KV_NOT_FOUND


def test_four_callers_race_and_each_reply_is_its_own(native):
    """Sessions of four callers interleave (in a pool that holds them all):
    every word is its request's, every reply of a session names the same
    admission, and the numbers are the order the table keeps."""
    out = []
    service = serve(sessions=16)

    def caller(c):
        ch = native.StepChannel(service.port, ici=True)
        try:
            for n in range(3):
                for layer in range(LAYERS):
                    x = layer_bytes(c * 100 + n * 10 + layer)
                    out.append((c * 10 + n, layer, x,
                                ch.put(c * 10 + n, layer, x)))
        finally:
            ch.close()

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(out) == 36
    assert all(got[0] == kv_reference.word(x) for _, _, x, got in out)
    admitted = {}
    for session, _, _, (_, a) in out:
        assert admitted.setdefault(session, a) == a  # the same in each reply
    assert sorted(admitted.values()) == list(range(12))
    assert list(service.table) == sorted(admitted, key=admitted.get)
    service.close()
    assert service.failure is None


@pytest.mark.parametrize("nbytes, layer", [(0, 0), (12, 0), (LAYER_BYTES + 8,
                                                             0), (64, LAYERS)])
def test_a_bad_put_fails_the_call_and_nothing_else(native, service, channel,
                                                   nbytes, layer):
    with pytest.raises(native.RpcError) as e:
        channel.put(1, layer, np.zeros(nbytes, dtype=np.uint8))
    assert e.value.code == native.TERR_REQUEST
    assert service.table == {}  # it took no slot
    x = layer_bytes(4)
    assert channel.put(1, 0, x) == (kv_reference.word(x), 0)
    assert service.failure is None


def test_each_service_refuses_the_others_methods(native, service, channel):
    import jax

    from brpc_tpu import tensor_service

    with pytest.raises(native.RpcError) as e:
        channel.call(np.zeros(64, dtype=np.uint8))
    assert e.value.code == native.TERR_NO_METHOD
    tensor = tensor_service.serve(jax.devices("cpu")[0], depth=2,
                                  max_bytes=4096, key=1)
    other = native.StepChannel(tensor.port, ici=True)
    try:
        with pytest.raises(native.RpcError) as e:
            other.put(1, 0, np.zeros(64, dtype=np.uint8))
        assert e.value.code == native.TERR_NO_METHOD
        with pytest.raises(native.RpcError) as e:
            other.get(1, 0, 64)
        assert e.value.code == native.TERR_NO_METHOD
    finally:
        other.close()
        tensor.close()
    assert service.failure is None and tensor.failure is None


def test_counters_spans_and_stages_of_a_put(native, service, channel):
    import json

    from brpc_tpu import spans

    def stages():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{service.port}/status?format=json",
                timeout=10) as r:
            return json.loads(r.read().decode())["stages"]

    before, stages0 = counters(service.port), stages()
    assert set(before) == {
        "rpc_kv_puts", "rpc_kv_gets", "rpc_kv_chunks", "rpc_kv_bytes_landed",
        "rpc_kv_evictions", "rpc_kv_failed", "rpc_kv_pool_bytes",
        "rpc_kv_resident_bytes", "rpc_kv_get_chunks", "rpc_kv_bytes_read",
        "rpc_kv_get_misses"}
    assert before["rpc_kv_pool_bytes"] == LAYERS * SLOTS * LAYER_BYTES
    assert before["rpc_kv_resident_bytes"] == 0
    spans.clear()
    for session in (1, 2, 3):  # the third evicts the first
        channel.put(session, 0, layer_bytes(session))
        channel.put(session, 1, layer_bytes(session, 40000))
    channel.get(3, 1, LAYER_BYTES)
    with pytest.raises(native.RpcError):
        channel.get(1, 0, LAYER_BYTES)
    after, stages1 = counters(service.port), stages()
    moved = {k: after[k] - before[k] for k in after}
    assert moved == {
        "rpc_kv_puts": 6, "rpc_kv_gets": 1, "rpc_kv_failed": 1,
        "rpc_kv_chunks": 3 * (4 + 3),
        "rpc_kv_bytes_landed": 3 * (LAYER_BYTES + 40000),
        "rpc_kv_evictions": 1, "rpc_kv_pool_bytes": 0,
        "rpc_kv_resident_bytes": 2 * (LAYER_BYTES + 40000),
        "rpc_kv_get_chunks": 3, "rpc_kv_bytes_read": 40000,
        "rpc_kv_get_misses": 1}
    for name, n in (("tdev.take_wait", 8), ("tdev.reply", 7),
                    ("trpc.handler", 8)):
        assert stages1[name]["count"] - stages0[name]["count"] == n, name
    span_names("kv.reply")
    records = spans.snapshot()
    names = [rec[0] for rec in records]
    assert {"kv.take", "kv.fill", "kv.join", "kv.reply", "kv.evict",
            "ring.launch", "ring.frame", "ring.h2d", "ring.kernel_dispatch",
            "ring.retire", "ring.d2h_wait", "ring.complete"} <= set(names)
    assert names.count("kv.evict") == 1 and names.count("kv.join") == 6
    # The taker fills, the dispatch thread steps, the completion thread
    # replies; a join belongs to none of them and is counted whole.
    owners = [{rec[4] for rec in records if rec[0] == name}
              for name in ("kv.fill", "ring.dispatch", "kv.reply")]
    assert [len(o) for o in owners] == [1, 1, 1]
    assert len(set.union(*owners)) == 3
    joins = [rec for rec in records if rec[0] == "kv.join"]
    own = spans.self_times(records)
    assert own["kv.join"] == pytest.approx(sum(r[2] - r[1] for r in joins))
    for _, start, end, put, _ in joins:
        fills = [r for r in records if r[0] == "kv.fill" and r[3] is put]
        assert fills and start <= min(r[1] for r in fills)
        assert end >= max(r[2] for r in fills)


def test_nothing_of_a_chunk_comes_back_but_its_word(native, service,
                                                    channel):
    """The lane's kernel contract (ISSUE 33): the put step's bulk result
    stays on the device, so the completion thread is handed no bytes."""
    seen = []
    landed = service.lane.on_done

    def watching(put, back, word, good):
        seen.append((back, word))
        landed(put, back, word, good)

    service.lane.on_done = watching
    x = layer_bytes(8)
    assert channel.put(9, 0, x)[0] == kv_reference.word(x)
    assert len(seen) == LAYER_BYTES // CHUNK
    assert all(back is None for back, _ in seen)
    assert sum(w for _, w in seen) & 0xFFFFFFFF == kv_reference.word(x)


def test_a_ring_abort_mid_call_fails_that_call_once_and_every_later_one(
        native, service):
    """The second chunk of a four-chunk Put meets an aborted ring on the
    dispatch thread: the call fails exactly once (rpc_kv_failed moves by
    one for it), what follows fails, the taker ends and close() joins."""
    from brpc_tpu import native as nat

    steps = []
    real = service._put_chunk

    def second_chunk_aborts(layer, where, x):
        steps.append(1)
        if len(steps) == 2:
            service.ring.abort()
            raise nat.RingAbortedError("aborted under the second chunk")
        return real(layer, where, x)

    service._put_chunk = second_chunk_aborts
    before = counters(service.port)
    ch = native.StepChannel(service.port, ici=True)
    try:
        with pytest.raises(native.RpcError) as e:
            ch.put(1, 0, layer_bytes(1))
        assert e.value.code == native.TERR_INTERNAL
        service._taker.join(timeout=5)
        assert not service._taker.is_alive()
        assert isinstance(service.failure, nat.RingAbortedError)
        for _ in range(2):
            with pytest.raises(native.RpcError) as e:
                ch.put(2, 0, layer_bytes(2))
            assert e.value.code == native.TERR_INTERNAL
        with pytest.raises(native.RpcError) as e:
            ch.get(1, 0, LAYER_BYTES)
        assert e.value.code == native.TERR_INTERNAL
    finally:
        ch.close()
    after = counters(service.port)
    assert after["rpc_kv_failed"] - before["rpc_kv_failed"] == 4
    assert after["rpc_kv_puts"] == before["rpc_kv_puts"]
    assert len(steps) == 2  # nothing was stepped after the abort


def test_a_device_error_under_four_callers_fails_each_call_once(native,
                                                                service):
    """A step that raises on the dispatch thread abandons its chunk and
    every chunk behind it: each call in flight fails once however many of
    its chunks were abandoned, and every caller is answered."""
    steps, out = [], []
    real = service._put_chunk

    def sixth_fails(layer, where, x):
        steps.append(1)
        if len(steps) == 6:
            raise RuntimeError("the step failed")
        return real(layer, where, x)

    service._put_chunk = sixth_fails
    before = counters(service.port)

    def caller(c):
        ch = native.StepChannel(service.port, ici=True)
        try:
            for layer in range(2):
                x = layer_bytes(c * 10 + layer)
                try:
                    out.append(ch.put(c, layer, x)[0]
                               == kv_reference.word(x))
                except native.RpcError as e:
                    out.append(e.code)
        finally:
            ch.close()

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(out) == 8 and out.count(True) == 1  # chunks 1-4: one call
    assert set(out) - {True} == {native.TERR_INTERNAL}
    assert "the step failed" in repr(service.failure)
    after = counters(service.port)
    assert after["rpc_kv_failed"] - before["rpc_kv_failed"] == 7
    assert after["rpc_kv_puts"] - before["rpc_kv_puts"] == 1
    service._taker.join(timeout=5)
    assert not service._taker.is_alive()


# ------------------------------------------- the Get leg (ISSUE 35)
#
# A pool of 3 layers x 3 sessions of 64 KiB, 16 KiB chunks: a Get is four
# read chunks through the lane, in submit order with the Puts' chunks.

GET_SLOTS = 3


@pytest.fixture
def store(native):
    svc = serve(sessions=GET_SLOTS)
    yield svc
    svc.close()


def test_gets_and_puts_interleaved_equal_the_reference(native, store):
    """A seeded mix of Puts and Gets over five sessions into three slots,
    through the lane: every word, admission, hit, miss and byte is the
    reference's."""
    rng = np.random.default_rng(35)
    ref = kv_reference.Cache(GET_SLOTS, LAYERS, LAYER_BYTES)
    ch = native.StepChannel(store.port, ici=True)
    try:
        for _ in range(120):
            session, layer = int(rng.integers(1, 6)), int(rng.integers(0, 3))
            if rng.random() < 0.4:
                n = 8 * int(rng.integers(1, LAYER_BYTES // 8 + 1))
                x = rng.integers(0, 256, n, dtype=np.uint8)
                assert ch.put(session, layer, x) == ref.put(session, layer,
                                                            x)
                continue
            try:
                want = ref.get(session, layer)
            except kv_reference.NotFound:
                with pytest.raises(native.RpcError) as e:
                    ch.get(session, layer, LAYER_BYTES)
                assert e.value.code == native.KV_NOT_FOUND
            else:
                assert ch.get(session, layer, LAYER_BYTES).tobytes() == want
    finally:
        ch.close()
    assert store.failure is None


def test_gets_beside_puts_from_four_threads_hit_exactly_or_miss(native,
                                                                store):
    """Four threads, each with sessions of its own, Put and Get at once,
    and sessions are evicted and admitted again all the while: a Get that
    returns bytes returns exactly its thread's last acknowledged Put of
    that layer, a miss is the not-found error, and afterwards the table
    holds the sessions of the last `slots` admissions, which every Put's
    reply named."""
    results, admissions, lock = [], {}, threading.Lock()

    def caller(c):
        rng = np.random.default_rng(100 + c)
        kept = {}  # (session, layer) -> the bytes last acknowledged
        ch = native.StepChannel(store.port, ici=True)
        try:
            for i in range(40):
                session = 10 * c + int(rng.integers(0, 3))
                layer = int(rng.integers(0, LAYERS))
                if i % 3 == 0 or (session, layer) not in kept:
                    x = rng.integers(0, 256, LAYER_BYTES, dtype=np.uint8)
                    word, admitted = ch.put(session, layer, x)
                    results.append(("put", word == kv_reference.word(x)))
                    kept[(session, layer)] = x.tobytes()
                    with lock:
                        assert admissions.setdefault(admitted,
                                                     session) == session
                    continue
                try:
                    got = ch.get(session, layer, LAYER_BYTES).tobytes()
                    results.append(("hit", got == kept[(session, layer)]))
                except native.RpcError as e:
                    results.append(("miss", e.code == native.KV_NOT_FOUND))
                    del kept[(session, layer)]
        finally:
            ch.close()

    threads = [threading.Thread(target=caller, args=(c,)) for c in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(results) == 160 and all(ok for _, ok in results)
    assert {kind for kind, _ in results} == {"put", "hit", "miss"}
    assert sorted(admissions) == list(range(store.admitted))
    last = sorted(admissions)[-GET_SLOTS:]
    assert {admissions[a] for a in last} == set(store.table)
    assert store.failure is None


def test_a_get_after_a_puts_reply_sees_it_and_one_during_a_re_put_misses(
        native, store):
    """Read after write holds by order on the one dispatch thread; while a
    layer is being put again it is not present, so a Get of it misses, and
    once the re-Put is answered a Get returns the new bytes."""
    ch = native.StepChannel(store.port, ici=True)
    ch2 = native.StepChannel(store.port, ici=True)
    try:
        old, new = layer_bytes(1), layer_bytes(2)
        ch.put(7, 1, old)
        assert ch.get(7, 1, LAYER_BYTES).tobytes() == old.tobytes()
        gate, entered = threading.Event(), threading.Event()
        real = store._put_chunk

        def held(layer, where, x):
            entered.set()
            gate.wait(30)
            return real(layer, where, x)

        store._put_chunk = held
        reput = threading.Thread(target=lambda: ch2.put(7, 1, new))
        reput.start()
        assert entered.wait(30)  # the re-Put's first chunk is being stepped
        with pytest.raises(native.RpcError) as e:
            ch.get(7, 1, LAYER_BYTES)
        assert e.value.code == native.KV_NOT_FOUND
        gate.set()
        reput.join(30)
        store._put_chunk = real
        assert ch.get(7, 1, LAYER_BYTES).tobytes() == new.tobytes()
    finally:
        ch.close()
        ch2.close()


def test_a_device_error_on_a_read_chunk_fails_its_get_once_and_all_behind(
        native, store):
    """The second read chunk of a Get raises on the dispatch thread: that
    Get fails once (rpc_kv_failed moves by one for it, whatever its four
    chunks do), every later call fails, and the taker ends."""
    ch = native.StepChannel(store.port, ici=True)
    try:
        ch.put(3, 0, layer_bytes(3))
        reads, real = [], store._read_chunk

        def second_read_fails(layer, where):
            reads.append(1)
            if len(reads) == 2:
                raise RuntimeError("the read failed")
            return real(layer, where)

        store._read_chunk = second_read_fails
        before = counters(store.port)
        with pytest.raises(native.RpcError) as e:
            ch.get(3, 0, LAYER_BYTES)
        assert e.value.code == native.TERR_INTERNAL
        store._taker.join(timeout=5)
        assert not store._taker.is_alive()
        for call in (lambda: ch.put(4, 0, layer_bytes(4)),
                     lambda: ch.get(3, 0, LAYER_BYTES)):
            with pytest.raises(native.RpcError) as e:
                call()
            assert e.value.code == native.TERR_INTERNAL
        after = counters(store.port)
    finally:
        ch.close()
    assert after["rpc_kv_failed"] - before["rpc_kv_failed"] == 3
    assert after["rpc_kv_gets"] == before["rpc_kv_gets"]
    assert after["rpc_kv_get_chunks"] - before["rpc_kv_get_chunks"] == 1
    assert "the read failed" in repr(store.failure)


def test_a_gets_reply_is_its_chunks_d2h_buffers_written_in_parts(
        native, store, monkeypatch):
    """The reply is assembled through the C API's part reply: one part a
    chunk, one write a chunk, each out of that chunk's own D2H buffer, and
    no concatenation anywhere."""
    ch = native.StepChannel(store.port, ici=True)
    seen, landed = [], store.lane.on_done

    def watching(token, back, word, good):
        seen.append(back)
        landed(token, back, word, good)

    def no_concatenate(*a, **k):
        raise AssertionError("a Get concatenated its chunks")

    def parts():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{store.port}/vars", timeout=10) as r:
            return {ln.split(" : ")[0]: int(ln.split(" : ")[1].split()[0])
                    for ln in r.read().decode().splitlines()
                    if ln.startswith("rpc_reply_part")}

    try:
        x = layer_bytes(5, 40000)  # three chunks, the last one short
        ch.put(5, 2, x)
        store.lane.on_done = watching
        monkeypatch.setattr(np, "concatenate", no_concatenate)
        before = parts()
        assert ch.get(5, 2, LAYER_BYTES).tobytes() == x.tobytes()
        after = parts()
    finally:
        ch.close()
    moved = {k: after[k] - before[k] for k in after}
    assert moved["rpc_reply_part_writes"] == 3
    assert moved["rpc_reply_parts_shared"] + moved["rpc_reply_parts_local"] \
        == 3
    assert len(seen) == 3 and len({id(b) for b in seen}) == 3
    assert all(b.nbytes == CHUNK for b in seen)


# ------------------------------------------- where a Get's reply parts lie

# The cell's order in a process of its own (the pool is one per process):
# four streams Put 9 MiB layers at once, whose received blocks carve the
# shared region's general area past the room for another 4 MiB slab arena
# (the next one lands outside the region); then a Get, whose 3 MiB parts
# are chains of the region's 8 KiB blocks; then a Get whose parts are
# taken while every free block of the region is held (native.region_hold),
# released once the parts fell back to process memory.
REGION_SCENARIO = """
import json, sys, threading, time, urllib.request
import numpy as np
sys.path.insert(0, {root!r})
import jax
from brpc_tpu import kv_service, native

MIB = 1 << 20
LAYER = 9 * MIB
svc = kv_service.serve(jax.devices("cpu")[0], layers=2, sessions=4,
                       layer_bytes=LAYER)

def fetch(path):
    with urllib.request.urlopen(f"http://127.0.0.1:{{svc.port}}{{path}}",
                                timeout=10) as r:
        return r.read().decode()

def state():
    got = {{ln.split(" : ")[0]: int(ln.split(" : ")[1].split()[0])
           for ln in fetch("/vars").splitlines()
           if ln.startswith("rpc_reply_part")}}
    (jumbo,) = [c for c in json.loads(fetch("/pools?format=json"))["classes"]
                if c["bytes"] == 4 * MIB]
    got["slots_carved"] = jumbo["carved"]
    return got

def moved(before):
    after = state()
    return {{k: after[k] - before[k] for k in after}}

def layer(stream, n):
    return np.random.default_rng(10 * stream + n).integers(
        0, 256, LAYER, dtype=np.uint8)

start = threading.Barrier(4)

def stream(s):
    ch = native.StepChannel(svc.port, ici=True)
    start.wait()
    for n in range(2):
        ch.put(s, n, layer(s, n))
    ch.close()

streams = [threading.Thread(target=stream, args=(s,)) for s in range(4)]
for t in streams:
    t.start()
for t in streams:
    t.join()
ch = native.StepChannel(svc.port, ici=True)
before = state()
equal = bool(np.array_equal(ch.get(2, 1, LAYER), layer(2, 1)))
print("SHARED", json.dumps(dict(moved(before), equal=equal)), flush=True)

gate, entered = threading.Event(), threading.Event()
real = svc._read_chunk

def gated(n, where):
    entered.set()
    gate.wait(30)
    return real(n, where)

svc._read_chunk = gated
answer = {{}}

def getter():
    answer["equal"] = bool(np.array_equal(ch.get(1, 0, LAYER), layer(1, 0)))

before = state()
t = threading.Thread(target=getter)
t.start()
entered.wait(30)  # the Get is in: its request needed the region too
held = native.region_hold(1 << 40)
gate.set()
deadline = time.monotonic() + 30
while (moved(before)["rpc_reply_parts_local"] < 3
       and time.monotonic() < deadline):
    time.sleep(0.005)
native.region_hold(-1)
t.join(30)
svc._read_chunk = real
print("LOCAL", json.dumps(dict(moved(before), held=held, **answer)),
      flush=True)
ch.close()
svc.close()
"""


@pytest.fixture(scope="module")
def region_scenario(cpp_build):
    import json
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-c",
         textwrap.dedent(REGION_SCENARIO.format(root=str(root)))],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=LIMIT_S, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {key: json.loads(rest) for key, _, rest in
            (ln.partition(" ") for ln in proc.stdout.splitlines())
            if key in ("SHARED", "LOCAL")}


def test_after_the_puts_a_gets_parts_are_the_regions_own_blocks(
        region_scenario):
    """The Puts' received blocks left no room in the region for another
    slab arena, so each 3 MiB part is a chain of the region's general 8 KiB
    blocks: all three shared (posted by reference), none in process memory,
    the bytes equal, and no slab slot of the region given to a part (the 4
    MiB class carved at most the one slot outside it, tried and returned)."""
    got = region_scenario["SHARED"]
    assert got["equal"] is True
    assert got["rpc_reply_part_writes"] == 3
    assert got["rpc_reply_parts_shared"] == 3
    assert got["rpc_reply_parts_local"] == 0
    assert got["slots_carved"] <= 1


def test_a_gets_parts_fall_back_to_process_memory_when_the_region_is_held(
        region_scenario):
    """Every free block of the region held while the parts are taken: all
    three are process memory, counted local, and the Get is still answered
    with its bytes once the hold is given back."""
    got = region_scenario["LOCAL"]
    assert got["held"] > 0
    assert got["rpc_reply_parts_local"] == 3
    assert got["rpc_reply_parts_shared"] == 0
    assert got["equal"] is True
