// The Transport tier: the first-class peer-endpoint seam of the stack.
//
// Two layers live here:
//
//  1. TransportEndpoint — the pluggable DATA-PLANE of one Socket: how an
//     ICI/shm queue-pair (or TLS) transport takes over reads and writes
//     while Socket keeps the id/lifecycle/wait-free-queue semantics.
//     Modeled on the role of reference src/brpc/rdma/rdma_endpoint.h: the
//     RDMA endpoint bypasses the fd write path (CutFromIOBufList
//     rdma_endpoint.cpp:777 posts IOBuf blocks as SGEs zero-copy),
//     delivers completions through a comp-channel fd registered with the
//     normal EventDispatcher (PollCq rdma_endpoint.cpp:1364), and rejoins
//     the standard InputMessenger parse pipeline
//     (input_messenger.cpp:416). The four pillars preserved here (SURVEY
//     §2.9): zero-copy block posting, windowed credit flow control, event
//     suppression/batched completions, completions unified into the one
//     event dispatcher.
//
//  2. TransportTier — the REGISTRY of endpoint types (ISSUE 12): fd/tcp,
//     in-process ici, cross-process shm, device staging — each described
//     once (name, descriptor capability, zero-copy, process scope) so
//     descriptor eligibility, credit-flow accounting, and byte
//     attribution live in ONE seam instead of per-transport special
//     cases scattered through socket/policy code. This is the layering
//     the reference's RDMA endpoint implies and the prerequisite for a
//     DCN-class tier: a new transport is a new registry entry + endpoint
//     implementation, not a fork of the data path.
#pragma once

#include <sys/types.h>

#include <cstdint>
#include <string>

#include "tbase/iobuf.h"

namespace tpurpc {

class Socket;

// ---- the transport tier registry ----

// Static properties of one peer-endpoint type. Registered once; the id
// is stable for the process lifetime and labels the per-tier
// rpc_transport_* attribution families.
struct TransportTier {
    const char* name = "";
    // One-sided pool descriptors may ride this transport: the peers'
    // handshake maps each other's registered pools (or the peer IS this
    // process), so a (pool_id, offset, len) reference resolves on the
    // other side. Send-side eligibility AND resolve-side scope both
    // consult this — the one seam deciding "may a payload cross as a
    // reference here".
    bool descriptor_capable = false;
    // Payload blocks post by reference (ring descriptors), not by copy
    // through a byte stream.
    bool zero_copy = false;
    // The peer lives in another process (its pool is mapped shm, not
    // this process's own allocator).
    bool cross_process = false;
    // One-sided verbs (ISSUE 18): REMOTE_READ/REMOTE_WRITE posted
    // against leased pool windows move data with ZERO remote CPU on the
    // data path (shm_xproc memcpy-direct today). Tiers without the bit
    // degrade to wire-emulated two-sided verbs through the same seam.
    bool one_sided = false;
    // Max scatter-gather entries one posted verb may carry (0 = no SGL;
    // a multi-block post must be emulated entry-by-entry).
    uint32_t sgl_max = 0;
};

// Register a tier; returns its id (stable, small). Re-registering an
// existing name returns the existing id. Bounded (16) — a runaway
// registration is a bug, not a workload.
int RegisterTransportTier(const TransportTier& t);
const TransportTier* GetTransportTier(int tier);  // null for bad ids
int FindTransportTier(const char* name);          // -1 when unknown
int TransportTierCount();

// Built-in tiers, registered lazily on first use (stable within a
// process; always present once any socket/pool code ran).
int TierTcp();       // plain fd byte stream (TLS included)
int TierIci();       // in-process queue-pair link (loopback ICI)
int TierShmXproc();  // cross-process shared-memory queue pair
int TierDevice();    // device staging ring (peer = the accelerator)
// Cross-pod data-center-network tier (ISSUE 14): a plain fd byte
// stream to a peer in ANOTHER pod. Descriptor-INCAPABLE — the peers
// share no pool mapping, so descriptor-pinned tries degrade to inline
// through the existing seam — and shaped by the -dcn_emu_* knobs so
// non-datacenter containers can emulate WAN latency/bandwidth.
int TierDcn();

// ---- emulated-WAN shaping for the dcn tier (ISSUE 14) ----
// Microseconds a writer should park before moving `bytes` on `tier`:
// -dcn_emu_latency_us (per write op) + bytes/-dcn_emu_mbps. 0 for
// non-dcn tiers or when both knobs are off. Per-connection shaping by
// design (each KeepWrite fiber sleeps independently) — the knob
// emulates a WAN pipe per flow, not an aggregate trunk.
int64_t DcnShapeDelayUs(int tier, size_t bytes);
// The inbound half: bytes/-dcn_emu_mbps ONLY — latency is charged once
// per message at the writer; read-burst boundaries are an artifact of
// kernel buffer sizes, not messages, so charging the fixed latency per
// read would tax a large transfer by how it happened to fragment.
int64_t DcnShapeReadDelayUs(int tier, size_t bytes);
// One relaxed check for the write hot path: true when any shaping knob
// is live (writers then route through the KeepWrite fiber, where
// sleeping is legal).
bool DcnShapingEnabled();

// ---- descriptor eligibility / scope (the one seam) ----

// The pool layer (tici/block_pool.cc Init) tells the transport tier how
// to name THIS process's shared pool without tnet depending on tici.
void SetLocalPoolIdProvider(uint64_t (*provider)());
uint64_t TransportLocalPoolId();  // 0 when no shared pool exists

// Send-side eligibility: may a pool descriptor (either direction) ride
// this socket? True exactly when the socket's tier is
// descriptor-capable — the peer either mapped our pool at handshake
// (cross-process tiers map both ways) or IS this process (in-process
// tiers resolve the local pool directly).
bool TransportDescriptorCapable(const Socket* s);

// Resolve-side scope: may a descriptor arriving ON this socket name
// `pool_id`? Only the pool this connection's handshake mapped
// (Socket::peer_pool_id) or — on an in-process transport — this
// process's own pool. The global pool registry alone must never
// authorize: any connection could otherwise name another tenant's
// mapped pool and read memory it was never handed.
bool TransportDescriptorScopeOk(const Socket* s, uint64_t pool_id);

// Verb eligibility (ISSUE 18): may one-sided verbs move data directly
// on this socket? Tier one_sided bit AND descriptor eligibility (a
// window is a pool reference, so the same pool-mapping evidence
// applies). False routes posts through the emulated two-sided path.
bool TransportOneSided(const Socket* s);
// The socket tier's sgl_max (0 when null/one-sided-incapable).
uint32_t TransportSglMax(const Socket* s);

// ---- per-tier byte/credit attribution ----
// Every transport's data-plane volume lands in one labelled family set
// (rpc_transport_{in,out}_bytes / rpc_transport_desc_{in,out}_bytes /
// rpc_transport_credit_stalls / rpc_transport_ops{transport=...}) so
// /pools and /metrics show WHERE bytes move without per-transport
// special cases. Hot paths add to pre-resolved cells — one relaxed
// fetch_add per call.
namespace transport_stats {
void AddIn(int tier, int64_t bytes);    // bytes received/pumped
void AddOut(int tier, int64_t bytes);   // bytes written/posted
void AddDescIn(int tier, int64_t bytes);   // descriptor-referenced, in
void AddDescOut(int tier, int64_t bytes);  // descriptor-referenced, out
void AddCreditStall(int tier);  // writer parked waiting for window credits
void AddOp(int tier);           // writes/pumps/ring completes

// Test/portal reads.
int64_t in_bytes(int tier);
int64_t out_bytes(int tier);
int64_t desc_in_bytes(int tier);
int64_t desc_out_bytes(int tier);
int64_t credit_stalls(int tier);
int64_t ops(int tier);

// One "tier <name> caps=... in=... out=... desc_in=... desc_out=...
// stalls=... ops=..." line per registered tier (the /pools section).
std::string DebugString();
// Register the labelled rpc_transport_* families eagerly (idempotent)
// so /metrics and the lint see them before the first byte moves.
void ExposeVars();
}  // namespace transport_stats

// ---- the per-socket data-plane endpoint ----

// Stage clock (tvar/stage_recorder.h): when one Pump call first and last
// found bytes posted. A long pump of pipelined messages brings the head
// of the next message with its last batch, the first bytes into an empty
// buffer with its first.
struct PumpStamps {
    int64_t first_us = 0;
    int64_t last_us = 0;
};

class TransportEndpoint {
public:
    virtual ~TransportEndpoint() = default;

    // The doorbell/completion fd. Registered with the EventDispatcher as
    // the Socket's fd (the comp-channel-fd pattern): readable when data
    // arrived or credits freed.
    virtual int event_fd() const = 0;

    // True once the endpoint can carry data (post-handshake).
    virtual bool Established() const = 0;

    // Post bytes from pieces[0..count) into the send queue, zero-copy:
    // block references are held by the queue until the remote side
    // completes them. Returns bytes posted (pieces are pop_front'd);
    // -1/EAGAIN when out of window credits; -1/other errno on failure.
    // Stage clock (tvar/stage_recorder.h): an endpoint that stamps its
    // posts sets *posted_us to the stamp it took as it published them, so
    // the socket's writer reuses that clock read; one that does not
    // leaves it alone (the caller passes 0 and reads the clock itself).
    virtual ssize_t CutFromIOBufList(IOBuf* const* pieces, size_t count,
                                     int64_t* posted_us = nullptr) = 0;

    // Block the calling fiber until credits may be available (woken by the
    // pump when the peer consumes). Returns 0, or -1 on timeout/failure.
    virtual int WaitWritable(int64_t abstime_us) = 0;

    // Drain the completion queue: move received bytes into *dst, release
    // send-side refs completed by the peer, wake writable waiters.
    // fd-read semantics: >0 bytes appended; 0 = peer closed (EOF);
    // -1/EAGAIN = nothing pending. *stamps: as *posted_us above.
    virtual ssize_t Pump(IOPortal* dst, PumpStamps* stamps = nullptr) = 0;

    // Half-close: peer's next drained Pump returns EOF. Idempotent.
    virtual void Close() = 0;

    // Drop the owner's reference (a Socket with owns_transport, or the
    // harness). The endpoint's backing link frees itself when every
    // endpoint is released — the socket and the peer's socket can tear
    // down in any order without dangling pipes.
    virtual void Release() {}

    // Which registry tier this endpoint belongs to. The TLS transport is
    // still the fd byte-stream tier (encrypted TCP); queue-pair
    // endpoints override with their own tier.
    virtual int tier() const { return TierTcp(); }
};

}  // namespace tpurpc
