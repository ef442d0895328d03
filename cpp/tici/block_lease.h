// Block leases: crash-safe ownership of pinned zero-copy pool blocks.
//
// A one-sided PoolDescriptor (rpc_meta.proto) tells the peer "read my
// pool at (offset, len)"; the sender must keep the underlying slab slot
// pinned until the RPC completes — and BEFORE this layer existed, the
// pin lived as a raw IOBuf ref inside the Controller, so a peer that
// died mid-RPC (or a wedged call that never reached EndRPC) leaked the
// slot forever: the classic dangling-pin hazard of RDMA-style data
// paths ("RPC Considered Harmful" §4, arXiv:1805.08430).
//
// The lease registry OWNS every pin:
//  - Pin() takes the pinned IOBuf (one contiguous pool block ref) and
//    hands back a lease id; the controller keeps only the id plus the
//    raw descriptor fields.
//  - Release(id) is exactly-once by construction: the first caller —
//    EndRPC, the expiry reaper, or peer-death reclamation — drops the
//    registry's ref (recycling the slab slot); later callers get false.
//    Double-release across the retry/backup re-issue flow is therefore
//    structurally impossible.
//  - Arm(id, call, deadline, peer) stamps the owning call id, an expiry
//    deadline derived from the RPC's propagated deadline (+ grace;
//    -pool_lease_default_ms bounds deadline-less calls), and the socket
//    the descriptor was posted on. Re-issues re-arm (new peer key).
//  - A reaper thread (started lazily at the first Pin; interval
//    -pool_lease_reap_ms) reclaims expired leases: rpc_pool_reaped /
//    rpc_pool_lease_expired count them, and the slab live count returns
//    to baseline even when EndRPC never runs.
//  - ReleasePeer(peer_key) frees every lease armed against a dead
//    peer's socket — called from the same failure-observer path that
//    already cancels that socket's server calls, so a SIGKILLed node
//    cannot strand pins on the survivors.
//
// Thread contract: plain std::mutex (called from fibers, Python threads
// through the C ABI, and the reaper thread alike — never holds the lock
// across user code). pb-free: links into the standalone pool suite.
#pragma once

#include <cstdint>
#include <string>

#include "tbase/iobuf.h"

namespace tpurpc {
namespace block_lease {

// The "no entitled peer" key. Peer keys are SocketIds and 0 IS one (the
// first socket a process creates), so the sentinel is the one value no
// live socket carries (== INVALID_VREF_ID; printed as -1 on /pools).
constexpr uint64_t kNoPeer = ~(uint64_t)0;

// Pin `buf` (ownership moves into the registry). Returns a nonzero
// lease id. The bytes stay readable by peers until the first Release.
// `direction` tags the lease for the /pools ledger: "req" = a client
// pinning a request attachment (released at EndRPC), "rsp" = a server
// pinning a response attachment (released by the client's desc_ack).
// Must be a string with static storage duration.
uint64_t Pin(IOBuf&& buf, const char* direction = "req");

// Stamp ownership + expiry on a pinned lease (idempotent). `deadline_us`
// is an absolute monotonic_time_us instant; <= 0 applies now +
// -pool_lease_default_ms. `add_peer=false` REPLACES the entitled-peer
// key (a retry: the previous try is finished); true ADDS it alongside
// the existing one (a backup request: the original try's peer may
// still read the block, so peer-death reclamation frees the pin only
// when EVERY entitled peer is gone — two keys held max). Returns false
// when the lease no longer exists (already released or reclaimed) —
// the arm IS the caller's liveness check, under the same lock, so no
// reclamation can land between a separate probe and the arm.
bool Arm(uint64_t lease_id, uint64_t call_id, int64_t deadline_us,
         uint64_t peer_key, bool add_peer = false);

// Exactly-once release: true when THIS call dropped the pin; false when
// the lease was already released (reaper / peer death / earlier call)
// or never existed.
bool Release(uint64_t lease_id);

// True while the lease still holds its pin.
bool Alive(uint64_t lease_id);

// Reap leases whose deadline has passed (the reaper thread's body, split
// out so tests can drive it with a fake `now`). Returns reaped count.
size_t ReapExpired(int64_t now_us);

// Release every lease armed with `peer_key` (socket failure observer /
// shm-link teardown). Returns released count.
size_t ReleasePeer(uint64_t peer_key);

// Release every lease armed with `call_id` AND entitled to `peer_key` —
// the response-direction completion: the client's desc_ack names the
// wire correlation id the server armed its response pin under, and the
// ack arrives on the very connection the descriptor left on. BOTH keys
// must match: correlation ids are only unique within one client
// process, so an unscoped release could free another connection's pin.
// Exactly-once like Release (a duplicate ack finds nothing). Returns
// released count. O(live leases) scan — the token-less fallback; acks
// carrying the descriptor's ack_token take the O(log n) ReleaseAcked
// path instead.
size_t ReleaseByCall(uint64_t call_id, uint64_t peer_key);

// O(log n) scoped release by the ack token (= the lease id the server
// embedded in the response descriptor): direct lookup, then the SAME
// call-id + entitled-peer validation as ReleaseByCall — a forged or
// cross-connection token frees nothing. True when this ack dropped the
// pin.
bool ReleaseAcked(uint64_t lease_id, uint64_t call_id,
                  uint64_t peer_key);

// Counters (also exposed as rpc_pool_{pinned_blocks,lease_expired,
// reaped,peer_released} tvars).
uint64_t pinned();         // live leases
uint64_t pins_total();     // lifetime Pin() calls
uint64_t released();       // releases via Release() (EndRPC path)
uint64_t expired_reaped(); // releases via ReapExpired
uint64_t peer_released();  // releases via ReleasePeer

// One "key value" line per stat + one "lease <id> dir=<req|rsp>
// call=<c> deadline_in_ms=<d> peer=<p>" line per live lease (the /pools
// page body; bounded to the first 64 leases).
std::string DebugString();

// JSON array of live leases with a direction column (the /pools
// ?format=json "leases" field; bounded to `max` entries).
std::string JsonLeases(size_t max);

// Start the background reaper thread (idempotent; Pin() calls it).
void StartReaper();

// Register the rpc_pool_* tvar families (idempotent; StartReaper and
// every portal-carrying Server call it so /metrics and the lint see
// the families even before the first pin).
void ExposeVars();

}  // namespace block_lease
}  // namespace tpurpc
