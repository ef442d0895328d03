"""tensor.Step served from this process (ISSUE 29): the C API's pull server
and blocking client, and `brpc_tpu.tensor_service` over them, held to the
plain reference exactly (integers: limit 0). CPU backend; every test has a
time limit of its own, so a hang fails here and stalls nothing."""
import signal
import threading
import time

import numpy as np
import pytest

from brpc_tpu import tensor_reference

KEY = 0xA5C3_9E17
MAX_BYTES = 1 << 20
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


@pytest.fixture
def service(native):
    import jax

    from brpc_tpu import tensor_service

    svc = tensor_service.serve(jax.devices("cpu")[0], depth=4,
                               max_bytes=MAX_BYTES, key=KEY)
    yield svc
    svc.close()


@pytest.fixture
def pull_server(native):
    server = native.PullServer()
    yield server
    server.stop()


def payload(rng, caller, seq, nbytes):
    """Bytes 0-7 carry caller and sequence number, the rest is random."""
    x = rng.integers(0, 256, nbytes, dtype=np.uint8)
    x[:8] = np.frombuffer(int((caller << 48) | seq).to_bytes(8, "little"),
                          dtype=np.uint8)
    return x


def call_and_compare(native, port, caller, sizes, seed, ici, out):
    """One caller: a call a size in `sizes`, each reply against the
    reference. Appends (caller, seq, verdict) to `out`."""
    rng = np.random.default_rng(seed + caller)
    channel = native.StepChannel(port, ici=ici)
    try:
        for seq, nbytes in enumerate(sizes, 1):
            x = payload(rng, caller, seq, nbytes)
            try:
                got = channel.call(x).tobytes()
                out.append((caller, seq,
                            got == tensor_reference.step(x, KEY)))
            except native.RpcError as e:
                out.append((caller, seq, e))
    finally:
        channel.close()


def run_callers(native, port, callers, sizes, seed=7, ici=True):
    out = []
    threads = [threading.Thread(target=call_and_compare,
                                args=(native, port, c, sizes, seed, ici, out))
               for c in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


# ------------------------------------------------------------ the reference

def test_reference_by_hand():
    x = np.array([7, 9, 1, 2], dtype="<u4")
    want_y = np.array([7, 9, 1 ^ 0xFF, 2 ^ 0xFF], dtype="<u4")
    want_w = (7 * 1 + 9 * 3 + 1 * 5 + 2 * 7) & 0xFFFFFFFF
    assert tensor_reference.step(x.tobytes(), 0xFF) == (
        want_y.tobytes() + want_w.to_bytes(4, "little"))
    big = np.full(6, 0xFFFFFFFF, dtype="<u4")
    w = sum(0xFFFFFFFF * (2 * j + 1) for j in range(6)) & 0xFFFFFFFF
    assert tensor_reference.step(big.tobytes(), 0)[-4:] == w.to_bytes(
        4, "little")


@pytest.mark.parametrize("nbytes", [0, 8, 12, 20])
def test_reference_refuses_a_size_the_service_refuses(nbytes):
    with pytest.raises(ValueError):
        tensor_reference.step(bytes(nbytes), KEY)


def test_reference_imports_nothing_of_the_served_path():
    import ast
    import inspect

    names = set()
    for node in ast.walk(ast.parse(inspect.getsource(tensor_reference))):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
    assert names == {"numpy"}


# ------------------------------------------- the pull server and the client

def test_take_waits_with_the_interpreter_lock_released(pull_server):
    """Another Python thread makes progress while `take` blocks in C++."""
    took = []
    taker = threading.Thread(
        target=lambda: took.append(pull_server.take(600_000)))
    t0 = time.monotonic()
    taker.start()
    spins = 0
    while taker.is_alive():
        spins += 1
    waited = time.monotonic() - t0
    taker.join()
    assert took == [None] and waited >= 0.5
    # Held, the lock would have let this loop run once or twice at most.
    assert spins > 10_000, spins


@pytest.mark.parametrize("ici", [False, True], ids=["tcp", "shm_link"])
def test_replies_out_of_order_each_reach_their_own_call(native, pull_server,
                                                        ici):
    """Two calls parked, the second answered first: each caller gets the
    answer made for its own request."""
    got = {}

    def caller(c):
        channel = native.StepChannel(pull_server.port, ici=ici)
        try:
            got[c] = channel.call(np.full(64, c, dtype=np.uint8)).tobytes()
        finally:
            channel.close()

    threads = [threading.Thread(target=caller, args=(c,)) for c in (1, 2)]
    for t in threads:
        t.start()
    calls = [pull_server.take(10_000_000) for _ in threads]
    assert all(c is not None and c.nbytes == 64 for c in calls)
    for call in reversed(calls):
        buf = np.empty(call.nbytes, dtype=np.uint8)
        call.copy_into(buf)
        call.reply(buf, np.array([buf[0]] * 4, dtype=np.uint8))
    for t in threads:
        t.join()
    assert got == {c: bytes([c]) * 68 for c in (1, 2)}


def tensor_counters(port, prefixes=("rpc_tensor_",)):
    import urllib.request

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/vars",
                                timeout=10) as r:
        return {ln.split(" : ")[0]: int(ln.split(" : ")[1].split()[0])
                for ln in r.read().decode().splitlines()
                if ln.startswith(prefixes)}


def test_an_answer_made_on_the_host_is_not_counted_as_a_step(native,
                                                             pull_server):
    """rpc_tensor_calls is not what a replier says of itself: `reply` has
    no say in it, only the service's completion of a step has."""
    before = tensor_counters(pull_server.port)
    got = []
    caller = threading.Thread(target=call_and_compare, args=(
        native, pull_server.port, 0, [4096], 11, True, got))
    caller.start()
    call = pull_server.take(10_000_000)
    x = np.empty(call.nbytes, dtype=np.uint8)
    call.copy_into(x)
    call.reply(np.frombuffer(tensor_reference.step(x, KEY), dtype=np.uint8))
    caller.join()
    assert got == [(0, 1, True)]  # even a right answer
    after = tensor_counters(pull_server.port)
    assert after["rpc_tensor_calls"] == before["rpc_tensor_calls"]
    assert after["rpc_tensor_bytes_in"] - before["rpc_tensor_bytes_in"] == 4096
    native.tensor_step_answered()
    assert (tensor_counters(pull_server.port)["rpc_tensor_calls"]
            == before["rpc_tensor_calls"] + 1)


@pytest.mark.parametrize("pad", [0, 4096], ids=["unpadded", "padded"])
@pytest.mark.parametrize("nbytes", [16, 4096 + 8, 1 << 20])
def test_copy_into_returns_the_crc32c_of_what_it_staged(native, pull_server,
                                                        nbytes, pad):
    """ISSUE 30: one walk over the request attachment's blocks (the larger
    sizes arrive over the shm link in many of them) copies each into the
    view and folds it into the crc; what is left of the view is zeroed and
    folded in the same call. The crc is that of request + zero tail."""
    x = np.random.default_rng(nbytes + pad).integers(0, 256, nbytes,
                                                     dtype=np.uint8)
    got = []
    caller = threading.Thread(target=lambda: got.append(
        native.StepChannel(pull_server.port, ici=True).call(x, 64)))
    caller.start()
    call = pull_server.take(10_000_000)
    assert call.nbytes == nbytes
    before = native.staging_counters()
    room = np.full(nbytes + pad + 8, 0xAA, dtype=np.uint8)
    view = room[:nbytes + pad]
    crc = call.copy_into(view)
    call.reply(view[:8])
    caller.join()
    staged = x.tobytes() + bytes(pad)
    assert view.tobytes() == staged and room[-8:].tobytes() == b"\xaa" * 8
    assert crc == native.crc32c(staged)
    after = native.staging_counters()
    assert (after["rpc_stage_fused_bytes"] - before["rpc_stage_fused_bytes"]
            == nbytes + pad)
    assert got[0].tobytes() == x[:8].tobytes()


def test_a_flag_is_the_embedding_processs_to_set(native):
    native.set_flag("socket_send_buffer_size", 1 << 20)
    native.set_flag("socket_recv_buffer_size", 1 << 20)
    with pytest.raises(ValueError):
        native.set_flag("no_such_flag_27", 1)
    with pytest.raises(ValueError):
        native.set_flag("socket_send_buffer_size", "a lot")


def test_stop_with_calls_parked_fails_them_and_joins(native):
    server = native.PullServer()
    out = []
    threads = [threading.Thread(target=call_and_compare,
                                args=(native, server.port, c, [64], 1, True,
                                      out)) for c in range(3)]
    for t in threads:
        t.start()
    held = server.take(10_000_000)  # one taken, the others parked behind it
    time.sleep(0.3)
    t0 = time.monotonic()
    stopper = threading.Thread(target=server.stop)
    stopper.start()  # fails the parked ones, then waits for the taken one
    time.sleep(0.2)
    held.fail(native.TERR_INTERNAL, "given up")
    stopper.join()
    for t in threads:
        t.join()
    assert time.monotonic() - t0 < 5
    codes = sorted(v.code for _, _, v in out)
    assert codes == [native.TERR_CLOSE, native.TERR_CLOSE,
                     native.TERR_INTERNAL]
    with pytest.raises(ValueError):
        held.reply(np.zeros(8, dtype=np.uint8))  # answered once, for good


def test_counters_and_stages_are_on_the_portal(native, service):
    import json
    import urllib.request

    def get(path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{service.port}{path}", timeout=10) as r:
            return r.read().decode()

    def counters():
        return tensor_counters(service.port)

    before = counters()
    assert set(before) == {"rpc_tensor_calls", "rpc_tensor_bytes_in",
                           "rpc_tensor_failed",
                           "rpc_tensor_parked_highwater"}
    stages0 = json.loads(get("/status?format=json"))["stages"]
    out = run_callers(native, service.port, 2, [4096, 4096, 20])
    assert sorted(v is True for _, _, v in out) == [False] * 2 + [True] * 4
    after = counters()
    assert after["rpc_tensor_calls"] - before["rpc_tensor_calls"] == 4
    assert after["rpc_tensor_failed"] - before["rpc_tensor_failed"] == 2
    assert (after["rpc_tensor_bytes_in"] - before["rpc_tensor_bytes_in"]
            == 4 * 4096 + 2 * 20)
    assert after["rpc_tensor_parked_highwater"] >= 1
    stages1 = json.loads(get("/status?format=json"))["stages"]
    for name, n in (("tdev.take_wait", 6), ("tdev.reply", 4),
                    ("trpc.handler", 6)):
        assert stages1[name]["count"] - stages0[name]["count"] == n, name
    # tdev.take_wait lies inside trpc.handler.
    assert (stages1["tdev.take_wait"]["sum_us"]
            - stages0["tdev.take_wait"]["sum_us"]
            <= stages1["trpc.handler"]["sum_us"]
            - stages0["trpc.handler"]["sum_us"])
    assert "loop " in get("/loops")


# ----------------------------------------------------------- the service

@pytest.mark.parametrize("callers", [1, 4])
@pytest.mark.parametrize("nbytes", [16, 4096, 65544, 1048576])
def test_served_replies_equal_the_reference(native, service, nbytes,
                                            callers):
    out = run_callers(native, service.port, callers, [nbytes] * 3,
                      seed=nbytes)
    assert len(out) == 3 * callers
    assert all(v is True for _, _, v in out), out
    assert service.failure is None


def test_mixed_sizes_from_many_callers_keep_reply_and_call_together(
        native, service):
    """Calls of different sizes in flight at once complete in whatever
    order the lane retires them; each reply belongs to its call."""
    from brpc_tpu import spans

    spans.clear()
    sizes = [16, 65544, 4096, 1048576, 24, 4096]
    out = run_callers(native, service.port, 4, sizes, seed=99)
    assert len(out) == 24 and all(v is True for _, _, v in out), out
    names = {rec[0] for rec in spans.snapshot()}
    assert {"tensor.take", "tensor.fill", "tensor.reply", "ring.launch",
            "ring.frame", "ring.h2d", "ring.kernel_dispatch", "ring.retire",
            "ring.d2h_wait", "ring.complete"} <= names
    assert "ring.stage" not in names and "ring.verify" not in names
    # One submitter (the taker), one dispatch thread, one completion
    # thread, for the server's life.
    owners = [{rec[4] for rec in spans.snapshot() if rec[0] == name}
              for name in ("ring.launch", "ring.dispatch", "tensor.reply")]
    assert [len(owner) for owner in owners] == [1, 1, 1]
    assert len(set.union(*owners)) == 3


def test_a_served_call_stages_in_one_pass_and_the_framer_walks_none(
        native, service):
    """ISSUE 30 on the served path: `tensor.fill` is the one pass over a
    call's bytes (attachment -> slot, zero tail, crc32c), once a call;
    `ring.frame` writes a header and a meta inside the same `ring.launch`;
    rpc_stage_fused_bytes moves by the sizes the calls crossed the chip at
    -- on /vars too."""
    from brpc_tpu import spans

    def on_vars():
        return tensor_counters(service.port, ("rpc_stage_fused_bytes",))

    spans.clear()
    before = native.staging_counters()
    assert on_vars() == before  # there from the first scrape
    sizes = [4096, 24, 65544, 1048576]  # staged at 4096, 4096, 131072, 1 MiB
    out = run_callers(native, service.port, 2, sizes, seed=30)
    assert len(out) == 8 and all(v is True for _, _, v in out), out
    after = native.staging_counters()
    assert on_vars() == after
    assert (after["rpc_stage_fused_bytes"] - before["rpc_stage_fused_bytes"]
            == 2 * (4096 + 4096 + 131072 + 1048576))
    by_call = {}
    for name, start, end, request, thread in spans.snapshot():
        if name in ("tensor.fill", "ring.frame", "ring.launch"):
            by_call.setdefault(request, []).append((name, start, end, thread))
    assert len(by_call) == 8
    for records in by_call.values():
        assert sorted(r[0] for r in records) == ["ring.frame", "ring.launch",
                                                 "tensor.fill"]
        (_, l0, l1, launcher), = [r for r in records if r[0] == "ring.launch"]
        for name, s0, s1, thread in records:
            assert l0 <= s0 and s1 <= l1 and thread == launcher, name


@pytest.mark.parametrize("max_bytes, want", [
    (16, [16]), (4096, [4096]), (4104, [4096, 4104]),
    (65544, [4096, 8192, 16384, 32768, 65536, 65544]),
    (1 << 20, [4096 << i for i in range(9)]),
])
def test_the_sizes_the_step_is_compiled_for(max_bytes, want):
    from brpc_tpu import tensor_service

    assert tensor_service.buckets(max_bytes) == want


def test_sizes_interleaved_from_four_callers_compile_nothing_under_a_call(
        native, service):
    """Whatever sizes the callers send, the step runs at one of the few
    shapes `serve` compiled before the first call: the launcher never
    waits for the compiler, so every call is far inside its deadline and
    the jit cache is as large after as before."""
    step = service.lane.kernel
    compiled = step._cache_size()
    assert compiled >= len(service.buckets) == 9
    sizes = [65544, 1048576, 24, 4104, 99992, 1048576, 262152, 65544] * 2
    t0 = time.monotonic()
    out = run_callers(native, service.port, 4, sizes, seed=5)
    assert len(out) == 64 and all(v is True for _, _, v in out), out
    assert time.monotonic() - t0 < 10  # 16 calls a caller in one deadline
    assert step._cache_size() == compiled
    assert service.failure is None


@pytest.mark.parametrize("nbytes", [0, 8, 20, 4100, MAX_BYTES + 8])
def test_a_bad_size_fails_the_call_and_nothing_else(native, service, nbytes):
    channel = native.StepChannel(service.port, ici=True)
    try:
        with pytest.raises(native.RpcError) as e:
            channel.call(np.zeros(nbytes, dtype=np.uint8))
        assert e.value.code == native.TERR_REQUEST
        x = payload(np.random.default_rng(3), 0, 1, 4096)
        assert channel.call(x).tobytes() == tensor_reference.step(x, KEY)
    finally:
        channel.close()
    assert service.failure is None


def test_close_with_calls_parked_fails_them_and_joins_in_time(native):
    import jax

    from brpc_tpu import tensor_service

    svc = tensor_service.serve(jax.devices("cpu")[0], depth=2,
                               max_bytes=4096, key=KEY)
    gate = threading.Event()
    submit = svc.lane.submit

    def held_up(fill, nbytes, token):
        gate.wait()
        return submit(fill, nbytes, token)

    svc.lane.submit = held_up
    out = []
    callers = [threading.Thread(target=call_and_compare,
                                args=(native, svc.port, c, [4096], 5, True,
                                      out)) for c in range(4)]
    for t in callers:
        t.start()
    time.sleep(0.5)  # one call in the taker's hands, three parked
    threads = threading.active_count()
    t0 = time.monotonic()
    closer = threading.Thread(target=svc.close)
    closer.start()
    time.sleep(0.2)
    gate.set()
    closer.join()
    for t in callers:
        t.join()
    assert time.monotonic() - t0 < 5
    verdicts = [v for _, _, v in out]
    assert verdicts.count(True) == 1  # the one in hand was answered
    assert sorted(v.code for v in verdicts if v is not True) == [
        native.TERR_CLOSE] * 3
    # The taker, the lane's two helpers, the four callers: all joined.
    assert threading.active_count() == threads - 3 - 4


def test_ring_abort_unblocks_take_and_fails_what_follows(native, service):
    from brpc_tpu import native as nat

    out = run_callers(native, service.port, 1, [4096])
    assert out[0][2] is True
    service.ring.abort()
    service._taker.join(timeout=5)  # parked in `take`, with no call coming
    assert not service._taker.is_alive()
    assert isinstance(service.failure, nat.RingAbortedError)
    out = run_callers(native, service.port, 2, [4096])
    assert sorted(v.code for _, _, v in out) == [native.TERR_INTERNAL] * 2


def test_a_device_error_fails_every_call_in_flight_and_joins(native,
                                                             service):
    class NeverComesBack:
        def __array__(self, *args, **kwargs):
            raise RuntimeError("the copy back failed")

    kernel = service.lane.kernel
    seen = []

    def failing(x):
        y, w = kernel(x)
        seen.append(1)
        return (NeverComesBack() if len(seen) == 2 else y), w

    service.lane.kernel = failing
    out = run_callers(native, service.port, 4, [4096, 4096])
    verdicts = [v for _, _, v in out]
    assert len(verdicts) == 8 and verdicts.count(True) <= 1 + 3
    failed = [v for v in verdicts if v is not True]
    assert failed and all(v.code == native.TERR_INTERNAL for v in failed)
    assert "copy back failed" in repr(service.failure)
    service._taker.join(timeout=5)
    assert not service._taker.is_alive()


def test_a_dispatch_error_fails_the_call_through_the_lanes_abandon(
        native, service):
    """ISSUE 32: the step is dispatched on the lane's dispatch thread, so
    its error no longer leaves `lane.submit`: it reaches the parked call as
    `on_abandon` on the completion thread, after the answers ahead of it,
    shuts the service and ends the taker; `close()` joins the lane's two."""
    seen, abandoned = [], []
    step = service.lane.kernel

    def second_fails(x):
        seen.append(threading.get_ident())
        if len(seen) == 2:
            raise RuntimeError("the dispatch failed")
        return step(x)

    service.lane.kernel = second_fails
    real_abandon = service.lane.on_abandon

    def abandon(call):
        abandoned.append(threading.get_ident())
        real_abandon(call)

    service.lane.on_abandon = abandon
    threads = threading.active_count()
    out = run_callers(native, service.port, 4, [4096, 4096])
    verdicts = [v for _, _, v in out]
    assert len(verdicts) == 8 and verdicts.count(True) == 1
    failed = [v for v in verdicts if v is not True]
    assert all(v.code == native.TERR_INTERNAL for v in failed)
    assert "dispatch failed" in repr(service.failure)
    assert service.failure is service.lane.failure
    # Met on the dispatch thread, handed to the call on the completion
    # thread: neither is the taker.
    assert abandoned and set(abandoned).isdisjoint(seen)
    assert service._taker.ident not in set(abandoned) | set(seen)
    service._taker.join(timeout=5)
    assert not service._taker.is_alive()
    helpers = list(service.lane._helpers)
    assert len(helpers) == 2
    service.close()
    assert not any(h.is_alive() for h in helpers)
    assert threading.active_count() == threads - 3


def test_an_h2d_error_fails_the_call_in_the_takers_hands(native, service,
                                                         monkeypatch):
    """The H2D is the taker's: its error leaves `lane.submit`, fails that
    call from the taker, shuts the service; the call ahead is answered."""
    from brpc_tpu import device_path

    real, seen = device_path._h2d, []

    def second_fails(view, dev):
        seen.append(threading.get_ident())
        if len(seen) == 2:
            raise RuntimeError("the H2D failed")
        return real(view, dev)

    monkeypatch.setattr(device_path, "_h2d", second_fails)
    out = run_callers(native, service.port, 4, [4096, 4096])
    verdicts = [v for _, _, v in out]
    assert len(verdicts) == 8 and verdicts.count(True) == 1
    assert all(v.code == native.TERR_INTERNAL for v in verdicts
               if v is not True)
    assert "H2D failed" in repr(service.failure)
    assert service.lane.failure is None and service.ring.aborted
    assert set(seen) == {service._taker.ident}
    service._taker.join(timeout=5)
    assert not service._taker.is_alive()
