"""Collective lowerings of the combo-channel family on a virtual 8-device
CPU mesh (conftest forces JAX_PLATFORMS=cpu + 8 host devices).

The C++ combo channels (cpp/trpc/combo_channels.h) fan calls out over
sockets; on a TPU mesh the same patterns lower to XLA collectives
(SURVEY §2.13): ParallelChannel fan-out == AllGather + ReduceScatter,
PartitionChannel sharding == sharded computation + psum merge.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.parallel.reference import coll_checksum, fill_deterministic


@pytest.fixture(scope="module")
def mesh():
    # Ask for the cpu backend explicitly: the environment may pin the
    # default platform to a single real accelerator, while this suite is
    # specified against the 8-device virtual host platform.
    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest should provide 8 virtual devices"
    return jax.sharding.Mesh(devices[:8], ("peers",))


def test_parallel_echo_roundtrip(mesh):
    from brpc_tpu.parallel.collective_echo import make_parallel_echo_step

    step = make_parallel_echo_step(mesh)
    payloads = jnp.arange(8 * 128, dtype=jnp.uint32).reshape(8, 128)
    out = step(payloads)
    # Fan-out + designated-responder + merge is an exact echo.
    np.testing.assert_array_equal(np.asarray(out), np.asarray(payloads))


def test_parallel_echo_is_exact_for_large_words(mesh):
    from brpc_tpu.parallel.collective_echo import make_parallel_echo_step

    step = make_parallel_echo_step(mesh)
    # Max-value words: a sum-based merge would overflow; the
    # designated-responder scheme must keep bits exact.
    payloads = jnp.full((8, 64), 0xFFFFFFFF, dtype=jnp.uint32)
    out = step(payloads)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(payloads))


def test_partition_echo_shards_and_checksums(mesh):
    from brpc_tpu.parallel.collective_echo import (
        _adler_frame_checksum,
        make_partition_echo_step,
    )

    step = make_partition_echo_step(mesh)
    payloads = jnp.arange(8 * 96, dtype=jnp.uint32).reshape(8, 96) * jnp.uint32(
        2654435761
    )
    check, echoed, total = step(payloads)
    np.testing.assert_array_equal(np.asarray(echoed), np.asarray(payloads))
    expected = _adler_frame_checksum(payloads)
    np.testing.assert_array_equal(np.asarray(check), np.asarray(expected))
    want_total = np.sum(np.asarray(expected), dtype=np.uint32)
    assert np.uint32(np.asarray(total)) == want_total


def test_partition_step_compiles_with_collective(mesh):
    from brpc_tpu.parallel.collective_echo import make_partition_echo_step

    step = make_partition_echo_step(mesh)
    payloads = jnp.ones((8, 32), dtype=jnp.uint32)
    compiled = step.lower(payloads).compile()
    hlo = compiled.as_text()
    # The psum merge must survive into the compiled module (the collective
    # rides ICI on hardware).
    assert "all-reduce" in hlo or "all_reduce" in hlo


# ---------------- ISSUE 13: mesh-collective lowerings ----------------

def test_coll_checksum_matches_cpp_golden():
    # Locked against Collective.ChecksumAndFillAreStable in
    # cpp/tests/tcollective_test.cc — one formula, two languages.
    assert coll_checksum([1, 2, 3]) == 1310726
    w = fill_deterministic(7, 9001, 2)
    assert int(w[0]) == (0x9E3779B1 * 7 + 0x85EBCA77 * 9001) % (1 << 32)
    assert int(w[1]) == (int(w[0]) + 0xC2B2AE35) % (1 << 32)


def test_allreduce_lowering_is_wraparound_sum(mesh):
    from brpc_tpu.parallel.collective_echo import make_allreduce_step

    step = make_allreduce_step(mesh)
    x = jnp.arange(8 * 64, dtype=jnp.uint32).reshape(8, 64) * jnp.uint32(
        2654435761
    )
    out = step(x)
    want = np.tile(np.asarray(x).sum(axis=0, dtype=np.uint32), (8, 1))
    np.testing.assert_array_equal(np.asarray(out), want)


def test_allgather_lowering_concatenates_rank_order(mesh):
    from brpc_tpu.parallel.collective_echo import make_allgather_step

    step = make_allgather_step(mesh)
    x = jnp.arange(8 * 32, dtype=jnp.uint32).reshape(8, 32)
    out = step(x)
    want = np.tile(np.asarray(x).reshape(-1), (8, 1))
    np.testing.assert_array_equal(np.asarray(out), want)


def test_alltoall_lowering_transposes_blocks(mesh):
    from brpc_tpu.parallel.collective_echo import make_alltoall_step

    step = make_alltoall_step(mesh)
    n, block = 8, 16
    x = jnp.arange(n * n * block, dtype=jnp.uint32).reshape(n, n * block)
    out = step(x)
    want = (
        np.arange(n * n * block, dtype=np.uint32)
        .reshape(n, n, block)
        .transpose(1, 0, 2)
        .reshape(n, n * block)
    )
    np.testing.assert_array_equal(np.asarray(out), want)


def _coll_command_round(nodes, alg, nbytes, seq, timeout=60.0):
    """Drive one collective round across every node and collect the
    per-node COLL result lines."""
    import json as _json
    import time as _time

    for n in nodes:
        n.send("coll %s %d %d" % (alg, nbytes, seq))
    results = []
    deadline = _time.time() + timeout
    for n in nodes:
        line = None
        while True:
            line = n._readline(deadline)
            assert line is not None, "node %d: no COLL line" % n.idx
            if line.startswith("COLL "):
                break
        results.append(_json.loads(line[5:]))
    return results


def test_cpp_mesh_allreduce_bitexact_vs_jax(cpp_build, tmp_path, mesh):
    """The C++ chunked-ring all-reduce over a real 4-process mesh must
    agree BIT FOR BIT with the XLA collective lowering on the same
    payloads (two implementations of one pattern)."""
    from test_chaos_soak import NODE_FLAGS, Node, _free_ports
    from brpc_tpu.parallel.collective_echo import make_allreduce_step

    binary = cpp_build / "mesh_node"
    assert binary.exists(), "mesh_node not built"
    num = 4
    ports = _free_ports(num)
    peers_file = tmp_path / "coll_members"
    peers_file.write_text("".join("127.0.0.1:%d\n" % p for p in ports))
    nodes = [
        Node(binary, ports[i], i, peers_file, flags=NODE_FLAGS,
             extra_args=("--collective",))
        for i in range(num)
    ]
    try:
        for n in nodes:
            assert n.wait_ready(), "node %d never became ready" % n.idx
        import time as _time
        _time.sleep(2.0)  # shm links establish

        seq, nbytes = 5, 64 * 1024
        nwords = nbytes // 4
        results = _coll_command_round(nodes, "allreduce", nbytes, seq)

        # Same payloads in JAX: row r = the deterministic fill of the
        # node with the r-th smallest port (the engine's rank order).
        rows = np.stack(
            [fill_deterministic(seq, p, nwords) for p in sorted(ports)]
        )
        step = make_allreduce_step(
            jax.sharding.Mesh(jax.devices("cpu")[:num], ("peers",))
        )
        jax_out = np.asarray(step(jnp.asarray(rows)))
        # The lowering agrees with the plain numpy wraparound sum...
        want = np.tile(rows.sum(axis=0, dtype=np.uint32), (num, 1))
        np.testing.assert_array_equal(jax_out, want)
        # ...and the C++ mesh produced the identical bits: checksum +
        # leading words on every node, nodes verified it internally too.
        expect_checksum = coll_checksum(want[0])
        expect_head = [int(v) for v in want[0][:4]]
        for rep in results:
            assert rep["ok"] == 1, rep
            assert rep["verified"] == 1, rep
            assert rep["nranks"] == num, rep
            assert rep["checksum"] == expect_checksum, rep
            assert rep["head"] == expect_head, rep

        for n in nodes:
            assert n.shutdown() == 0, "node %d unclean exit" % n.idx
    finally:
        for n in nodes:
            try:
                n.proc.kill()
            except OSError:
                pass
