"""The RPC data-plane computation, single-chip and mesh-parallel.

Single-chip `echo_step` models what the framework does to every payload:
frame it (length + checksum header) and echo it back. The mesh version
`make_parallel_echo_step` is the ParallelChannel fan-out lowered to XLA
collectives: every peer gathers all requests (AllGather = the fan-out of
parallel_channel.cpp:40 ParallelChannelDone), computes its response share,
and the responses are reduce-scattered back to their callers (= the
ResponseMerger of parallel_channel.h:151).

All shapes are static; control flow is compiler-friendly (no Python
branching on data), so XLA tiles the reductions onto the VPU and rides ICI
for the collectives.
"""

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from brpc_tpu import compile_cache

# Every step below is jitted: place the persistent compile cache before
# the first of them compiles.
compile_cache.enable()

_MOD = jnp.uint32(65521)


def _adler_frame_checksum(words: jax.Array) -> jax.Array:
    """Order-dependent checksum over uint32 words (last axis), vectorized.

    Plays the role crc32c plays in the reference's baidu_std frames
    (src/brpc/policy/crc32c_checksum.*): an order-sensitive integrity word.
    Split into 16-bit halves so all arithmetic stays in uint32 without
    overflow (<= 2048 halves * 65535 < 2^32), and computed with cumulative
    sums so it maps to parallel scans on TPU instead of a sequential loop.
    """
    lo = words & jnp.uint32(0xFFFF)
    hi = words >> jnp.uint32(16)
    halves = jnp.stack([lo, hi], axis=-1).reshape(*words.shape[:-1], -1)
    s1 = jnp.cumsum(halves, axis=-1)
    a = s1[..., -1] % _MOD
    b = jnp.sum(s1 % _MOD, axis=-1) % _MOD
    return (b << jnp.uint32(16)) | a


@jax.jit
def echo_step(payloads: jax.Array) -> tuple:
    """Frame + echo a batch of payloads: returns (checksums, lengths, echoed).

    payloads: uint32[batch, words].
    """
    checksums = _adler_frame_checksum(payloads)
    # The echo "service": identity transform on the payload (the reference's
    # echo example, example/echo_c++/server.cpp), plus a framed length word.
    lengths = jnp.full(
        (payloads.shape[0],), payloads.shape[1] * 4, dtype=jnp.uint32
    )
    return checksums, lengths, payloads


def make_parallel_echo_step(mesh: Mesh):
    """ParallelChannel fan-out over a mesh: AllGather -> serve -> ReduceScatter.

    Returns a jitted step: uint32[n_peers, words] -> uint32[n_peers, words]
    where row i is peer i's merged response.
    """
    axis = mesh.axis_names[0]

    def _shard_body(local: jax.Array) -> jax.Array:
        # local: uint32[1, words] — this peer's outbound request.
        # Fan-out: every peer sees all requests (the sub-channel sends of
        # ParallelChannel, lowered to one AllGather over ICI).
        all_reqs = jax.lax.all_gather(local, axis, axis=0, tiled=True)
        # Each request i is served by its designated responder, peer
        # (i+1) mod n — a real remote hop. Non-responders contribute zeros,
        # so the ReduceScatter merge below routes exactly one response back
        # to each caller with no arithmetic on payload bits (a uint32 sum
        # of n copies would wrap for words >= 2^32/n).
        n = jax.lax.axis_size(axis)
        me = jax.lax.axis_index(axis)
        req_idx = jnp.arange(n, dtype=jnp.uint32)
        is_responder = ((req_idx + 1) % n) == me.astype(jnp.uint32)
        served = jnp.where(is_responder[:, None], all_reqs, jnp.uint32(0))
        # Merge responses back to callers (ResponseMerger): ReduceScatter
        # sums one nonzero contribution per caller row == exact echo.
        merged = jax.lax.psum_scatter(
            served, axis, scatter_dimension=0, tiled=True
        )
        return merged

    sharded = jax.shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis, None),
    )
    return jax.jit(sharded)


def make_allreduce_step(mesh: Mesh):
    """The mesh all-reduce as XLA lowers it (ISSUE 13 cross-check).

    The C++ collective tier (cpp/trpc/collective.h) runs the same
    pattern as a chunked descriptor-pipelined ring over the RPC mesh;
    both implementations compute a uint32 WRAPAROUND sum, so their
    results must agree bit for bit on identical payloads
    (tests/test_collectives.py drives both).

    Returns a jitted step: uint32[n, words] -> uint32[n, words] where
    every row holds the elementwise sum over rows.
    """
    axis = mesh.axis_names[0]

    def _shard_body(local: jax.Array) -> jax.Array:
        # local: uint32[1, words]. psum == the ring's reduce; uint32
        # arithmetic wraps identically on every backend.
        return jax.lax.psum(local, axis)

    sharded = jax.shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis, None),
    )
    return jax.jit(sharded)


def make_allgather_step(mesh: Mesh):
    """The mesh all-gather lowering: every row collects all rows.

    Twin of the C++ pull-based chunked all-gather. Returns a jitted
    step: uint32[n, words] -> uint32[n, n*words] (per-row concatenation
    of every peer's block, rank order).
    """
    axis = mesh.axis_names[0]

    def _shard_body(local: jax.Array) -> jax.Array:
        g = jax.lax.all_gather(local, axis, axis=0, tiled=True)
        return g.reshape(1, -1)

    sharded = jax.shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis, None),
    )
    return jax.jit(sharded)


def make_alltoall_step(mesh: Mesh):
    """The mesh all-to-all lowering: block i of row r lands on row i.

    Twin of the C++ pairwise-exchange all-to-all (lower rank initiates,
    the reply carries the reciprocal block). Returns a jitted step:
    uint32[n, n*block] -> uint32[n, n*block] where the output row r is
    the concatenation of every rank's block-for-r.
    """
    axis = mesh.axis_names[0]

    n = mesh.shape[axis]

    def _shard_body(local: jax.Array) -> jax.Array:
        # local: uint32[1, n*block] -> [n, block] blocks by destination;
        # tiled all_to_all swaps block j of rank r with block r of rank j.
        blocks = local.reshape(n, -1)
        exchanged = jax.lax.all_to_all(
            blocks, axis, split_axis=0, concat_axis=0, tiled=True
        )
        return exchanged.reshape(1, -1)

    sharded = jax.shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=P(axis, None),
    )
    return jax.jit(sharded)


def make_partition_echo_step(mesh: Mesh):
    """PartitionChannel sharding lowered to XLA: each peer owns one shard.

    The C++ PartitionChannel (cpp/trpc/combo_channels.h; reference
    src/brpc/partition_channel.h:34) splits one logical service across M
    partitions and fans every call out to all of them, merging the
    responses. On a mesh that IS sharded computation: requests are laid
    out with one partition per device (jax.sharding), each device serves
    its shard (frame checksum + echo), and the "merge" is the sharded
    output itself — XLA inserts the collectives only where the layout
    demands them.

    Returns a jitted step: uint32[n_parts, words] ->
    (uint32[n_parts], uint32[n_parts, words], uint32[]): per-partition
    checksums, echoed shards, and the cluster-wide merged integrity word
    (the psum that rides ICI on hardware).
    """
    axis = mesh.axis_names[0]

    def _shard_body(local: jax.Array):
        # local: uint32[parts_per_device, words] — this device's shard.
        check = _adler_frame_checksum(local)
        # Cross-partition integrity word (the fan-out's merged status):
        # one psum over ICI, the cheapest possible "ResponseMerger".
        total = jax.lax.psum(jnp.sum(check, dtype=jnp.uint32), axis)
        return check, local, total

    sharded = jax.shard_map(
        _shard_body,
        mesh=mesh,
        in_specs=P(axis, None),
        out_specs=(P(axis), P(axis, None), P()),
    )
    return jax.jit(sharded)
