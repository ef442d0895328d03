// IciLink: a software queue pair — the loopback ICI transport.
//
// Plays the role reference src/brpc/rdma/rdma_endpoint.{h,cpp} plays over
// verbs, with the same four design pillars (SURVEY §2.9):
//   1. zero-copy block posting: the sender moves IOBuf BlockRefs into the
//      send ring (refs held in the ring — the `_sbuf` equivalent,
//      rdma_endpoint.cpp:777 CutFromIOBufList) and releases them only
//      after the receiver's consumed counter passes them (the remote
//      completion, rdma_endpoint.cpp:937 HandleCompletion).
//   2. windowed credit flow control: ring depth = the window; consumed
//      counts are published back like piggybacked ACKs
//      (rdma_endpoint.cpp:907 SendAck / window fields h:256-261).
//   3. event suppression: the doorbell eventfd is only signaled when the
//      consumer armed it (solicited-event flag; CQ arm/disarm pattern).
//   4. completions unified into the dispatcher: each endpoint's eventfd is
//      registered with the normal EventDispatcher as the Socket's fd, so
//      the upper stack is transport-agnostic (comp-channel-fd pattern,
//      rdma_endpoint.cpp:1364 PollCq feeding InputMessenger).
//
// The "DMA" is performed at the receiver: Pump copies posted spans into
// pool blocks appended to the socket's IOPortal (one copy per byte — what
// the interconnect DMA engine does in hardware; loopback TCP pays four).
// On a real TPU-VM this class is the seam where libtpu transfer queues
// slot in: post -> ici enqueue, Pump -> completion-queue drain, the
// rings' shared counters -> device doorbells. Cross-host setup runs the
// same handshake-over-DCN scheme as the RDMA endpoint (GID/QPN exchange
// over TCP, rdma_endpoint.h:127) — see IciHandshake below.
#pragma once

#include <atomic>
#include <cstdint>
#include <memory>

#include "tbase/iobuf.h"
#include "tnet/transport.h"

namespace tpurpc {

class IciLink;

namespace ici_internal {

// One direction of the link. Single producer (the socket's elected
// writer), single consumer (the socket's input-event fiber).
struct Pipe {
    static constexpr uint32_t kDepth = 256;  // the flow-control window

    struct Desc {
        IOBuf::Block* block;  // producer holds one ref until released
        uint32_t offset;
        uint32_t length;
    };

    Desc ring[kDepth];
    char pad0[64];
    std::atomic<uint64_t> head{0};      // producer: next slot to fill
    char pad1[64];
    std::atomic<uint64_t> tail{0};      // consumer: slots [.,head) pending
    char pad2[64];
    std::atomic<bool> closed{false};
    // Event suppression: consumer arms before sleeping; producer signals
    // the doorbell only when armed (batched completions otherwise).
    std::atomic<bool> rx_armed{true};
    // Producer parked waiting for window credits; consumer rings the
    // producer's doorbell when it consumes.
    std::atomic<bool> tx_waiting{false};

    // Refs freed up to this slot. Advanced ONLY after the dec_refs are
    // done (single claimer via `releasing`): the producer's reuse window
    // is bounded by `released`, so a slot is never overwritten while its
    // old block pointer is still pending a dec_ref.
    std::atomic<uint64_t> released{0};
    std::atomic<bool> releasing{false};

    // Producer credits: bounded by RELEASED (not consumed) slots — a
    // consumed-but-unreleased slot still holds an owned block pointer.
    uint32_t credits() const {
        return kDepth - (uint32_t)(head.load(std::memory_order_relaxed) -
                                   released.load(std::memory_order_acquire));
    }
};

}  // namespace ici_internal

// One side of an IciLink. Implements the Socket transport seam.
class IciEndpoint : public TransportEndpoint {
public:
    int event_fd() const override { return evfd_; }
    bool Established() const override;
    ssize_t CutFromIOBufList(IOBuf* const* pieces, size_t count,
                             int64_t* posted_us = nullptr) override;
    int WaitWritable(int64_t abstime_us) override;
    ssize_t Pump(IOPortal* dst, PumpStamps* stamps = nullptr) override;
    void Close() override;
    void Release() override;  // link frees itself after both sides release
    int tier() const override { return TierIci(); }

    // Doorbell signal count (tests: event-suppression assertions).
    uint64_t signals_sent() const {
        return signals_sent_.load(std::memory_order_relaxed);
    }

private:
    friend class IciLink;
    IciEndpoint() = default;

    void ReleaseCompleted();  // free sent refs the peer consumed

    IciLink* link_ = nullptr;
    ici_internal::Pipe* out_ = nullptr;  // we produce
    ici_internal::Pipe* in_ = nullptr;   // we consume
    int evfd_ = -1;                      // our doorbell (Socket's fd)
    IciEndpoint* peer_ = nullptr;
    void* writable_butex_ = nullptr;
    std::atomic<uint64_t> signals_sent_{0};
};

// A connected pair of endpoints (the fake-ICI "cable"). In-process for
// tests/bench; the shm + handshake-over-DCN variant keeps this exact
// layout in a MAP_SHARED segment.
//
// Lifetime: heap-only (Create). Each endpoint carries one owner
// reference (typically a Socket created with owns_transport); the link
// deletes itself when both are Release()d, so the two sockets can fail
// and recycle in any order without dangling pipes.
class IciLink {
public:
    static IciLink* Create() { return new IciLink; }

    IciEndpoint* first() { return &a_; }
    IciEndpoint* second() { return &b_; }

private:
    friend class IciEndpoint;
    IciLink();
    ~IciLink();
    void EndpointReleased();

    ici_internal::Pipe ab_;  // a produces, b consumes
    ici_internal::Pipe ba_;
    IciEndpoint a_;
    IciEndpoint b_;
    std::atomic<int> live_endpoints_{2};
};

}  // namespace tpurpc
