// Cross-process ICI link: a queue pair between two PROCESSES, bootstrapped
// by a handshake over TCP — the cross-host shape of the ICI transport.
//
// Mirrors the reference RDMA endpoint's lifecycle exactly (SURVEY §2.9,
// reference src/brpc/rdma/rdma_endpoint.h:127-130): a plain TCP connection
// performs the handshake — here exchanging shared-memory segment names
// instead of GID/QPN — then the data plane runs over registered memory
// while TCP stays idle as the failure detector. On a real multi-host
// TPU-VM deployment the peer-pool mapping becomes libtpu transfer-engine
// registration and the descriptor rings become ICI send/recv queues; the
// handshake, framing, credit flow control and teardown logic are
// identical.
//
// Memory layout:
//  - Each process's IciBlockPool primary region is a named POSIX shm
//    segment (its "registered memory", block_pool.h). The handshake
//    exchanges the two names; each side maps the peer's pool READ-ONLY.
//  - Per link, the CLIENT creates a small control segment holding two
//    ShmPipe descriptor rings (client->server and server->client). A
//    posted descriptor is (offset into sender's pool, length); the
//    receiver resolves it against its mapping of the sender's pool and
//    copies once into its IOPortal (what the interconnect DMA engine
//    does in hardware).
//  - Doorbells ride the TCP connection as single bytes (event-suppressed:
//    only sent when the other side armed), so completions enter the
//    normal EventDispatcher through the socket's fd — pillar 4, and the
//    reason peer death is detected for free (TCP EOF/RST).
//
// Send blocks not inside the shared pool region (pre-pool allocations,
// overflow regions) are bounce-copied into pool blocks — the same rule
// the reference applies to non-registered memory.
#pragma once

#include <atomic>
#include <cstdint>

#include "tbase/endpoint.h"
#include "tbase/iobuf.h"
#include "tnet/socket.h"
#include "tnet/transport.h"

namespace tpurpc {

class InputMessenger;

namespace shm_internal {

// One direction of the link, living in the shared control segment.
// Single producer (sender's elected writer fiber), single consumer
// (receiver's input-event fiber). POD + lock-free atomics only: this
// struct is shared between processes.
struct ShmPipe {
    static constexpr uint32_t kDepth = 1024;  // flow-control window

    struct Desc {
        uint64_t off;  // byte offset into the SENDER's pool shm segment
        uint32_t len;
        uint32_t pad;
    };

    alignas(64) std::atomic<uint64_t> head;  // producer: next slot to fill
    alignas(64) std::atomic<uint64_t> tail;  // consumer: [tail,head) pending
    alignas(64) std::atomic<uint32_t> closed;
    // Event suppression: consumer arms before sleeping; producer sends a
    // TCP doorbell byte only when armed.
    std::atomic<uint32_t> rx_armed;
    // Producer parked on credits; consumer sends a doorbell after
    // consuming when set.
    std::atomic<uint32_t> tx_waiting;
    Desc ring[kDepth];

    void InitPipe() {
        head.store(0, std::memory_order_relaxed);
        tail.store(0, std::memory_order_relaxed);
        closed.store(0, std::memory_order_relaxed);
        rx_armed.store(1, std::memory_order_relaxed);
        tx_waiting.store(0, std::memory_order_relaxed);
    }
};

// The control segment (created by the connecting client).
struct ShmLinkCtrl {
    static constexpr uint64_t kMagic = 0x49434954'4c4e4b31ull;  // "ICITLNK1"
    uint64_t magic;  // set LAST by the creator
    uint32_t version;
    uint32_t pad;
    ShmPipe c2s;  // client produces
    ShmPipe s2c;  // server produces
};

// Handshake frames exchanged over the TCP connection before the data
// plane starts (the ProcessHandshakeAtClient/AtServer analog).
//
// Version 2 (ISSUE 10): the structs grew a raw pool_epoch field, which
// changes their SIZE — and the exchange is a fixed-size raw read, so a
// version-1 peer would either starve the parser (shorter request) or
// leave trailing bytes to be mis-sniffed (longer one). The bumped
// version makes the mismatch an explicit clean rejection instead; the
// "epoch 0 = fence disabled" escape below is for same-size forward
// compatibility only.
constexpr uint32_t kIciHandshakeVersion = 2;

struct HandshakeRequest {
    char magic[4];  // "TICI"
    uint32_t version;
    char pool_name[64];  // client's pool shm segment
    uint64_t pool_size;
    char link_name[64];  // control segment (created by client)
    uint64_t link_size;
    // Pool generation at handshake time (epoch fencing, ISSUE 10b): the
    // receiver records it on the mapping; descriptors carrying a
    // different epoch are fenced with TERR_STALE_EPOCH. 0 from
    // pre-epoch binaries = fence disabled for that peer.
    uint64_t pool_epoch;
};

struct HandshakeResponse {
    char magic[4];  // "TICJ"
    uint32_t status;     // 0 = ok, else terrno
    char pool_name[64];  // server's pool shm segment
    uint64_t pool_size;
    uint64_t pool_epoch;  // server pool generation (see HandshakeRequest)
};

// Process-global registry of mapped peer pools (one mapping per peer
// process, shared by every link to it, refcounted).
struct PeerPool {
    char* base;
    size_t size;
};
// `epoch` is the owner's pool generation announced in the handshake
// (registered with the mapping for the stale-descriptor fence).
int AcquirePeerPool(const char* name, size_t size, uint64_t epoch,
                    PeerPool* out);
void ReleasePeerPool(const char* name);
// True when `name` is a safe single-component shm name ("/x...").
bool valid_shm_name(const char* name);

}  // namespace shm_internal

// One side of a cross-process link. The socket's fd IS the bootstrap TCP
// connection: doorbell bytes and peer-death events arrive through the
// normal dispatcher.
class ShmIciEndpoint : public TransportEndpoint {
public:
    int event_fd() const override { return tcp_fd_; }
    bool Established() const override;
    ssize_t CutFromIOBufList(IOBuf* const* pieces, size_t count,
                             int64_t* posted_us = nullptr) override;
    int WaitWritable(int64_t abstime_us) override;
    ssize_t Pump(IOPortal* dst, PumpStamps* stamps = nullptr) override;
    void Close() override;
    void Release() override;
    int tier() const override { return TierShmXproc(); }

    uint64_t signals_sent() const {
        return signals_sent_.load(std::memory_order_relaxed);
    }

    // Build one side. Takes ownership of tcp_fd and of the ctrl mapping;
    // acquires a ref on the peer pool (released in Release()).
    // `is_client`: which pipe this side produces into. `peer` is the
    // remote's endpoint (server address on the client side, ephemeral
    // peer address on the server side) — used for per-peer
    // fault-injection scoping (tnet/fault_injection.h).
    static ShmIciEndpoint* Create(int tcp_fd, void* ctrl_mapping,
                                  size_t ctrl_size, bool is_client,
                                  const char* peer_pool_name,
                                  const shm_internal::PeerPool& peer_pool,
                                  const EndPoint& peer);

private:
    ShmIciEndpoint() = default;
    ~ShmIciEndpoint() override;

    void ReleaseCompleted();
    void SendDoorbell();

    int tcp_fd_ = -1;
    EndPoint peer_ep_;  // fault-injection scoping identity
    shm_internal::ShmLinkCtrl* ctrl_ = nullptr;
    size_t ctrl_size_ = 0;
    shm_internal::ShmPipe* out_ = nullptr;
    shm_internal::ShmPipe* in_ = nullptr;
    char peer_pool_name_[64] = "";
    char* peer_base_ = nullptr;
    size_t peer_size_ = 0;
    // Sender-local shadow of the out ring: the block (one ref held) each
    // posted descriptor points into — the `_sbuf` of the RDMA endpoint.
    IOBuf::Block* sbuf_[shm_internal::ShmPipe::kDepth] = {};
    std::atomic<uint64_t> released_{0};  // refs freed up to this slot
    std::atomic<bool> releasing_{false};
    std::atomic<bool> tcp_eof_{false};  // failure detector tripped
    void* writable_butex_ = nullptr;
    std::atomic<uint64_t> signals_sent_{0};
};

// Client side: TCP-connect to `server`, run the handshake, and produce a
// connected Socket whose data plane is the shared-memory queue pair.
// Returns 0 and fills *id on success; -1 with errno/log on failure.
// Requires IciBlockPool::Init() with a shared primary region.
int IciConnect(const EndPoint& server, InputMessenger* messenger,
               SocketId* id, int timeout_ms = 3000);

// Server side: protocol index of the handshake sniffer (registered by
// GlobalInitializeOrDie; Server::StartNoListen adds it to the messenger
// so any accepted TCP connection can upgrade to the shm data plane).
int IciHandshakeProtocolIndex();
void RegisterIciHandshakeProtocol();  // idempotent; called from global init

}  // namespace tpurpc
