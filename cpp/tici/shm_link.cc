#include "tici/shm_link.h"

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <unistd.h>

#include <cerrno>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <string>

#include "tbase/errno.h"
#include "tbase/fast_rand.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tfiber/butex.h"
#include "tfiber/fiber.h"
#include "tici/block_pool.h"
#include "tnet/fault_injection.h"
#include "tnet/input_messenger.h"
#include "tvar/reducer.h"
#include "tvar/stage_recorder.h"

namespace tpurpc {

using shm_internal::HandshakeRequest;
using shm_internal::HandshakeResponse;
using shm_internal::PeerPool;
using shm_internal::ShmLinkCtrl;
using shm_internal::ShmPipe;

namespace shm_internal {

// ---------------- peer pool registry ----------------

namespace {
struct PeerPoolEntry {
    char* base;
    size_t size;
    int refs;
};
// Immortal singletons: endpoint Release() runs from Socket recycling,
// which a static Server's destructor can trigger during exit — after
// ordinary statics are gone. Leak the registry so teardown order can't
// use-after-free it.
std::mutex& pp_mu() {
    static std::mutex* mu = new std::mutex;
    return *mu;
}
std::map<std::string, PeerPoolEntry>& peer_pools() {
    static auto* m = new std::map<std::string, PeerPoolEntry>;
    return *m;
}

}  // namespace

// shm names must be a single path component ("/name"): reject anything
// else before it reaches shm_open (applies to peer-supplied pool AND
// link names).
bool valid_shm_name(const char* name) {
    if (name[0] != '/' || name[1] == '\0') return false;
    for (const char* c = name + 1; *c; ++c) {
        if (*c == '/') return false;
    }
    return strnlen(name, 64) < 64;
}

int AcquirePeerPool(const char* name, size_t size, uint64_t epoch,
                    PeerPool* out) {
    if (!valid_shm_name(name) || size == 0 || size > (4ull << 30)) {
        errno = EINVAL;
        return -1;
    }
    std::lock_guard<std::mutex> g(pp_mu());
    auto& pools = peer_pools();
    auto it = pools.find(name);
    if (it != pools.end()) {
        if (it->second.size < size) {
            errno = EINVAL;  // peer reported a bigger pool than we mapped
            return -1;
        }
        ++it->second.refs;
        // A later link re-announcing a NEWER generation re-stamps the
        // shared mapping: the owner remapped/restarted parts of its
        // pool, and descriptors minted before the bump must now fence.
        // Monotonic (RaiseEpoch): a slow handshake whose response was
        // written BEFORE the owner's bump must not regress the epoch
        // and re-admit genuinely stale descriptors.
        if (epoch != 0) {
            const uint64_t id = pool_registry::IdFromName(name);
            if (id != IciBlockPool::pool_id()) {
                pool_registry::RaiseEpoch(id, epoch);
            }
        }
        out->base = it->second.base;
        out->size = it->second.size;
        return 0;
    }
    const int fd = shm_open(name, O_RDONLY, 0);
    if (fd < 0) return -1;
    struct stat st;
    if (fstat(fd, &st) != 0 || (size_t)st.st_size < size) {
        close(fd);
        errno = EINVAL;
        return -1;
    }
    // Read-only: the receiver only resolves descriptors against the
    // peer's registered memory; it never writes into it.
    void* mem = mmap(nullptr, size, PROT_READ, MAP_SHARED, fd, 0);
    close(fd);
    if (mem == MAP_FAILED) return -1;
    pools[name] = PeerPoolEntry{(char*)mem, size, 1};
    // One-sided descriptors (ISSUE 9): mapping a peer pool IS the
    // memory registration descriptors resolve against — publish it
    // under the peer's pool id so a (pool_id, offset, len) meta field
    // from this peer reads in place. Our OWN pool (an in-process
    // loopback handshake maps it too) keeps its Init-time registration:
    // overwriting it with this transient mapping would let a later
    // link teardown unregister the local pool for good.
    const uint64_t id = pool_registry::IdFromName(name);
    if (id != IciBlockPool::pool_id()) {
        pool_registry::Register(id, (char*)mem, size,
                                epoch != 0 ? epoch : 1);
        // The verbs layer remaps peer pools O_RDWR by NAME for granted
        // REMOTE_WRITE windows (this handshake mapping is read-only).
        pool_registry::SetName(id, name);
    }
    out->base = (char*)mem;
    out->size = size;
    return 0;
}

void ReleasePeerPool(const char* name) {
    std::lock_guard<std::mutex> g(pp_mu());
    auto& pools = peer_pools();
    auto it = pools.find(name);
    if (it == pools.end()) return;
    if (--it->second.refs == 0) {
        const uint64_t id = pool_registry::IdFromName(name);
        if (id != IciBlockPool::pool_id()) {
            pool_registry::Unregister(id);
        }
        munmap(it->second.base, it->second.size);
        pools.erase(it);
    }
}

}  // namespace shm_internal

// ---------------- endpoint ----------------

namespace {
// Safety-net pair of the writer's credit wait (WaitWritable): cumulative
// /vars integers. The pump itself has no timed re-check: it runs on the
// doorbell's epoll edge only.
LazyAdder g_credit_wait_timeouts("rpc_link_credit_wait_timeouts");
LazyAdder g_credit_wait_timeouts_found_work(
    "rpc_link_credit_wait_timeouts_found_work");
}  // namespace

ShmIciEndpoint* ShmIciEndpoint::Create(int tcp_fd, void* ctrl_mapping,
                                       size_t ctrl_size, bool is_client,
                                       const char* peer_pool_name,
                                       const PeerPool& peer_pool,
                                       const EndPoint& peer) {
    auto* e = new ShmIciEndpoint;
    e->tcp_fd_ = tcp_fd;
    e->peer_ep_ = peer;
    e->ctrl_ = (ShmLinkCtrl*)ctrl_mapping;
    e->ctrl_size_ = ctrl_size;
    e->out_ = is_client ? &e->ctrl_->c2s : &e->ctrl_->s2c;
    e->in_ = is_client ? &e->ctrl_->s2c : &e->ctrl_->c2s;
    snprintf(e->peer_pool_name_, sizeof(e->peer_pool_name_), "%s",
             peer_pool_name);
    e->peer_base_ = peer_pool.base;
    e->peer_size_ = peer_pool.size;
    e->writable_butex_ = butex_create();
    *g_credit_wait_timeouts << 0;  // on /vars from the first scrape
    *g_credit_wait_timeouts_found_work << 0;
    return e;
}

ShmIciEndpoint::~ShmIciEndpoint() {
    // Free refs of posted-but-never-consumed descriptors (our own blocks;
    // the peer may be gone).
    if (out_ != nullptr) {
        const uint64_t head = out_->head.load(std::memory_order_acquire);
        for (uint64_t i = released_.load(std::memory_order_relaxed);
             i < head; ++i) {
            IOBuf::Block* b = sbuf_[i % ShmPipe::kDepth];
            if (b != nullptr) b->dec_ref();
        }
    }
    if (ctrl_ != nullptr) munmap(ctrl_, ctrl_size_);
    if (peer_pool_name_[0] != '\0') {
        shm_internal::ReleasePeerPool(peer_pool_name_);
    }
    if (tcp_fd_ >= 0) close(tcp_fd_);
    if (writable_butex_ != nullptr) butex_destroy(writable_butex_);
}

bool ShmIciEndpoint::Established() const {
    return !tcp_eof_.load(std::memory_order_acquire) &&
           out_->closed.load(std::memory_order_acquire) == 0 &&
           in_->closed.load(std::memory_order_acquire) == 0;
}

void ShmIciEndpoint::SendDoorbell() {
    // One byte on the bootstrap TCP connection: wakes the peer's
    // dispatcher, which pumps. EAGAIN (buffer full of doorbells) means
    // the peer stopped draining — the TCP failure detector covers that;
    // dropping the byte here is safe because a stuck peer re-arms and a
    // dead one never reads again.
    const char b = 'D';
    ssize_t r = send(tcp_fd_, &b, 1, MSG_NOSIGNAL | MSG_DONTWAIT);
    (void)r;
    signals_sent_.fetch_add(1, std::memory_order_relaxed);
}

void ShmIciEndpoint::ReleaseCompleted() {
    // Single claimer (writer fiber vs pump fiber); `released_` advances
    // only after the dec_refs are done so no slot is reused while its
    // old block pointer is pending — same protocol as the in-process
    // link (ici_link.cc).
    bool expected = false;
    if (!releasing_.compare_exchange_strong(expected, true,
                                            std::memory_order_acquire)) {
        return;
    }
    // Clamp to our own head: the tail counter is peer-writable shared
    // memory; a corrupt/hostile value past head must not dec_ref slots
    // still pending consumption (use-after-free) or overshoot the
    // credit window.
    const uint64_t head = out_->head.load(std::memory_order_relaxed);
    uint64_t consumed = out_->tail.load(std::memory_order_acquire);
    if (consumed > head) consumed = head;
    const uint64_t from = released_.load(std::memory_order_relaxed);
    for (uint64_t i = from; i < consumed; ++i) {
        IOBuf::Block* b = sbuf_[i % ShmPipe::kDepth];
        sbuf_[i % ShmPipe::kDepth] = nullptr;
        if (b != nullptr) b->dec_ref();
    }
    released_.store(consumed, std::memory_order_release);
    releasing_.store(false, std::memory_order_release);
}

ssize_t ShmIciEndpoint::CutFromIOBufList(IOBuf* const* pieces, size_t count,
                                         int64_t* posted_us) {
    if (!Established()) {
        errno = EPIPE;
        return -1;
    }
    ReleaseCompleted();
    ShmPipe* p = out_;
    uint64_t head = p->head.load(std::memory_order_relaxed);
    const uint64_t limit =
        released_.load(std::memory_order_acquire) + ShmPipe::kDepth;
    ssize_t posted = 0;
    size_t pending_bytes = 0;
    for (size_t i = 0; i < count; ++i) pending_bytes += pieces[i]->size();
    if (pending_bytes == 0) {
        return 0;  // all-empty pieces: match writev-on-empty semantics
    }
    // Chaos seam (tnet/fault_injection.h), scoped by the link's peer.
    FaultAction fault;
    size_t post_cap = (size_t)-1;
    bool corrupt_next = false;
    if (__builtin_expect(fault_injection_enabled(), 0)) {
        fault = FaultInjection::Decide(FaultOp::kWrite, peer_ep_,
                                       pending_bytes);
        switch (fault.kind) {
            case FaultAction::kReset:
                errno = ECONNRESET;
                return -1;
            case FaultAction::kDelay:
                // Safe to park: with chaos enabled, Socket::FlushOnce
                // routes every write through the KeepWrite fiber.
                fiber_usleep(fault.delay_us);
                break;
            case FaultAction::kDrop:
                for (size_t i = 0; i < count; ++i) {
                    pieces[i]->pop_front(pieces[i]->size());
                }
                return (ssize_t)pending_bytes;  // claimed, never posted
            case FaultAction::kShort:
                post_cap = fault.max_bytes > 0 ? fault.max_bytes : 1;
                break;
            case FaultAction::kCorrupt:
                // Force the first fragment through the bounce path so
                // the flip lands in OUR copy, never in a shared source
                // block.
                corrupt_next = true;
                break;
            default:
                break;
        }
    }
    for (size_t i = 0; i < count && head < limit; ++i) {
        IOBuf* buf = pieces[i];
        while (head < limit && !buf->empty() && (size_t)posted < post_cap) {
            ShmPipe::Desc& d = p->ring[head % ShmPipe::kDepth];
            size_t flen = 0;
            const char* fdata = buf->backing_block_data(0, &flen);
            uint64_t off;
            if (!corrupt_next && IciBlockPool::OffsetOf(fdata, &off)) {
                // Zero-copy: the bytes already live in our registered
                // (shared) region; post the offset and hold the block ref
                // until the peer's consumed counter passes it.
                IOBuf::BlockRef ref;
                buf->cut_front_ref(&ref);
                d.off = off;
                d.len = ref.length;
                sbuf_[head % ShmPipe::kDepth] = ref.block;
            } else {
                // Bounce: copy into a block guaranteed inside the shared
                // region (non-registered source memory — same rule as the
                // reference RDMA path). create_block() won't do: the TLS
                // cache / freelist may hand back an overflow-region block
                // the peer can't see.
                void* mem = IciBlockPool::AllocateSharedBlock();
                if (mem == nullptr && posted > 0) {
                    // Descriptors already written must not sit behind a
                    // reclaim wait: publish them now; the caller's
                    // normal backpressure retries the rest.
                    break;
                }
                if (mem == nullptr) {
                    // Shared blocks are circulating through per-thread
                    // caches; the failed call raised the pool's pressure
                    // flag (block_pool.cc), which reroutes them back to
                    // the shared freelist as they free. Flush our own
                    // cache and give the rest a short grace to drain.
                    // (Blocks parked in IDLE threads' caches stay out of
                    // reach — the dedicated bounce band exists precisely
                    // so that worst case is bounded to ring-depth bytes.)
                    IOBuf::flush_tls_cache();
                    for (int spin = 0;
                         spin < 50 && mem == nullptr; ++spin) {
                        mem = IciBlockPool::AllocateSharedBlock();
                        if (mem == nullptr) fiber_usleep(1000);
                    }
                }
                if (mem == nullptr) {
                    if (posted > 0) break;  // publish what we have
                    LOG(ERROR) << "ShmIciEndpoint: shared pool region "
                                  "exhausted; cannot bounce-copy";
                    errno = ENOMEM;
                    return -1;
                }
                auto* b = new (mem) IOBuf::Block;
                b->nshared.store(1, std::memory_order_relaxed);
                b->size = 0;
                b->cap = (uint32_t)(IOBuf::DEFAULT_BLOCK_SIZE -
                                    offsetof(IOBuf::Block, data));
                b->portal_next = nullptr;
                // Distinct deallocator: returns to the shared freelist,
                // never the TLS cache (see DeallocateShared).
                b->dealloc = IciBlockPool::DeallocateShared;
                uint64_t boff = 0;
                IciBlockPool::OffsetOf(b->data, &boff);
                const size_t n =
                    flen < (size_t)b->cap ? flen : (size_t)b->cap;
                buf->copy_to(b->data, n, 0);
                buf->pop_front(n);
                if (corrupt_next && n > 0) {
                    b->data[fault.aux % n] ^= 0x20;  // our bounce copy
                    corrupt_next = false;
                }
                d.off = boff;
                d.len = (uint32_t)n;
                sbuf_[head % ShmPipe::kDepth] = b;
            }
            posted += d.len;
            ++head;
        }
    }
    if (posted == 0) {
        errno = EAGAIN;  // window full: real back-pressure
        return -1;
    }
    // Stage clock: one read for the whole post, taken as the descriptors
    // are published (bounce copies above are the writer's own work, not
    // the hand-off's). Its low 32 bits ride each descriptor's spare word;
    // the peer's pump takes tici.link_handoff from them, the socket's
    // writer takes tnet.write_queue from *posted_us.
    const int64_t now_us = stage::now_us();
    if (posted_us != nullptr) *posted_us = now_us;
    const uint32_t post_stamp = stage::Low32(now_us);
    for (uint64_t i = p->head.load(std::memory_order_relaxed); i < head;
         ++i) {
        p->ring[i % ShmPipe::kDepth].pad = post_stamp;
    }
    p->head.store(head, std::memory_order_release);
    if (p->rx_armed.exchange(0, std::memory_order_acq_rel) != 0) {
        SendDoorbell();
    }
    return posted;
}

int ShmIciEndpoint::WaitWritable(int64_t abstime_us) {
    ShmPipe* p = out_;
    std::atomic<int>* word = butex_word(writable_butex_);
    const int expected = word->load(std::memory_order_acquire);
    p->tx_waiting.store(1, std::memory_order_release);
    // Fold consumed slots into released_ before the credit re-check (the
    // consume may have landed before tx_waiting was visible — no doorbell
    // was sent for it).
    ReleaseCompleted();
    const uint32_t credits =
        ShmPipe::kDepth -
        (uint32_t)(p->head.load(std::memory_order_relaxed) -
                   released_.load(std::memory_order_acquire));
    if (credits > 0 || !Established()) {
        p->tx_waiting.store(0, std::memory_order_release);
        return Established() ? 0 : -1;
    }
    if (butex_wait(writable_butex_, expected, &abstime_us) == ETIMEDOUT) {
        // Safety net: the caller's timed re-check, not the peer's
        // doorbell, ended the credit wait; credits free by now mean the
        // wake-up was lost.
        *g_credit_wait_timeouts << 1;
        ReleaseCompleted();
        if (p->head.load(std::memory_order_relaxed) -
                released_.load(std::memory_order_acquire) <
            ShmPipe::kDepth) {
            *g_credit_wait_timeouts_found_work << 1;
        }
    }
    p->tx_waiting.store(0, std::memory_order_release);
    // Timeout is not fatal (same contract as WaitEpollOut): the caller
    // re-checks and re-arms. Only a dead link is an error.
    return Established() ? 0 : -1;
}

ssize_t ShmIciEndpoint::Pump(IOPortal* dst, PumpStamps* stamps) {
    // 1. Drain doorbell bytes off the TCP connection; EOF/RST here is the
    //    failure detector (peer process died or closed).
    char tbuf[512];
    while (true) {
        const ssize_t r = recv(tcp_fd_, tbuf, sizeof(tbuf), MSG_DONTWAIT);
        if (r > 0) continue;
        if (r == 0) {
            tcp_eof_.store(true, std::memory_order_release);
            break;
        }
        if (errno == EINTR) continue;
        if (errno == EAGAIN || errno == EWOULDBLOCK) break;
        tcp_eof_.store(true, std::memory_order_release);  // RST etc.
        break;
    }
    // 2. Send-side completions: free refs the peer consumed, wake writers
    //    (they re-check credits; spurious wakes are harmless).
    ReleaseCompleted();
    butex_word(writable_butex_)->fetch_add(1, std::memory_order_release);
    butex_wake_all(writable_butex_);

    // Chaos seam: inbound faults on the resolved descriptor payloads.
    FaultAction fault;
    if (__builtin_expect(fault_injection_enabled(), 0)) {
        fault = FaultInjection::Decide(FaultOp::kRead, peer_ep_, 0);
        if (fault.kind == FaultAction::kReset) {
            tcp_eof_.store(true, std::memory_order_release);
            errno = ECONNRESET;
            return -1;
        }
        if (fault.kind == FaultAction::kDelay) {
            fiber_usleep(fault.delay_us);
        }
    }

    // 3. Receive: resolve descriptors against the peer's registered
    //    memory and copy once into dst (the "DMA").
    ShmPipe* p = in_;
    ssize_t received = 0;
    while (true) {
        uint64_t tail = p->tail.load(std::memory_order_relaxed);
        const uint64_t head = p->head.load(std::memory_order_acquire);
        if (tail == head) {
            if (received > 0) return received;
            if (p->closed.load(std::memory_order_acquire) != 0 ||
                tcp_eof_.load(std::memory_order_acquire)) {
                return 0;  // EOF only after the ring is drained
            }
            // Arm the doorbell, then re-check (a post may race the arm).
            p->rx_armed.store(1, std::memory_order_seq_cst);
            if (p->head.load(std::memory_order_seq_cst) != tail ||
                p->closed.load(std::memory_order_acquire) != 0) {
                continue;
            }
            errno = EAGAIN;
            return -1;
        }
        // Stage clock: one read per batch of descriptors found posted;
        // the messenger starts a message's clock at the batch that
        // brought its first bytes.
        const int64_t consume_us = stage::now_us();
        if (stamps != nullptr) {
            if (received == 0) stamps->first_us = consume_us;
            stamps->last_us = consume_us;
        }
        while (tail != head) {
            const ShmPipe::Desc d = p->ring[tail % ShmPipe::kDepth];
            stage::Add(stage::kLinkHandoff,
                       stage::Elapsed32(consume_us, d.pad));
            // Bounds-check against the mapped peer region: a corrupt or
            // hostile descriptor must not read out of the mapping.
            if (d.off > peer_size_ || d.len > peer_size_ - d.off) {
                LOG(ERROR) << "ShmIciEndpoint: descriptor out of bounds "
                           << d.off << "+" << d.len << " > " << peer_size_;
                tcp_eof_.store(true, std::memory_order_release);
                errno = TERR_REQUEST;
                return -1;
            }
            if (fault.kind == FaultAction::kDrop) {
                // Consume without delivering: the bytes vanish (the
                // sender's credits are still returned).
            } else if (fault.kind == FaultAction::kCorrupt &&
                       received == 0 && d.len > 0) {
                // Flip one byte of the first fragment via a copy window
                // (the peer's pool is mapped read-only).
                char window[512];
                const size_t wn =
                    d.len < sizeof(window) ? d.len : sizeof(window);
                memcpy(window, peer_base_ + d.off, wn);
                window[fault.aux % wn] ^= 0x20;
                dst->append(window, wn);
                if (d.len > wn) {
                    dst->append(peer_base_ + d.off + wn, d.len - wn);
                }
            } else {
                dst->append(peer_base_ + d.off, d.len);
            }
            received += d.len;
            ++tail;
            p->tail.store(tail, std::memory_order_release);
            if (fault.kind == FaultAction::kShort) {
                // Short read: deliver only this first descriptor; the
                // rest stays ring-buffered for the next pump.
                if (p->tx_waiting.load(std::memory_order_acquire) != 0) {
                    SendDoorbell();
                }
                return received;
            }
        }
        // Consumed -> credits freed on the peer: ring its doorbell if its
        // writer parked (piggybacked-ACK wakeup).
        if (p->tx_waiting.load(std::memory_order_acquire) != 0) {
            SendDoorbell();
        }
    }
}

void ShmIciEndpoint::Close() {
    if (out_->closed.exchange(1, std::memory_order_acq_rel) == 0) {
        // Wake the peer's pump (sees closed after draining) and our own
        // parked writers. shutdown() makes the close visible through the
        // failure detector even if the peer never reads the shm flag.
        SendDoorbell();
        shutdown(tcp_fd_, SHUT_WR);
        butex_word(writable_butex_)->fetch_add(1, std::memory_order_release);
        butex_wake_all(writable_butex_);
    }
}

void ShmIciEndpoint::Release() { delete this; }

// ---------------- client connect ----------------

namespace {

int write_all_timeout(int fd, const void* data, size_t n, int timeout_ms) {
    const char* p = (const char*)data;
    const int64_t deadline = monotonic_time_us() + timeout_ms * 1000ll;
    while (n > 0) {
        const ssize_t r = send(fd, p, n, MSG_NOSIGNAL);
        if (r > 0) {
            p += r;
            n -= (size_t)r;
            continue;
        }
        if (r < 0 && (errno == EINTR)) continue;
        if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
            if (monotonic_time_us() >= deadline) {
                errno = ETIMEDOUT;
                return -1;
            }
            pollfd pfd{fd, POLLOUT, 0};
            poll(&pfd, 1, 20);
            continue;
        }
        return -1;
    }
    return 0;
}

int read_all_timeout(int fd, void* data, size_t n, int timeout_ms) {
    char* p = (char*)data;
    const int64_t deadline = monotonic_time_us() + timeout_ms * 1000ll;
    while (n > 0) {
        const ssize_t r = recv(fd, p, n, 0);
        if (r > 0) {
            p += r;
            n -= (size_t)r;
            continue;
        }
        if (r == 0) {
            errno = ECONNRESET;
            return -1;
        }
        if (errno == EINTR) continue;
        if (errno == EAGAIN || errno == EWOULDBLOCK) {
            if (monotonic_time_us() >= deadline) {
                errno = ETIMEDOUT;
                return -1;
            }
            pollfd pfd{fd, POLLIN, 0};
            poll(&pfd, 1, 20);
            continue;
        }
        return -1;
    }
    return 0;
}

}  // namespace

int IciConnect(const EndPoint& server, InputMessenger* messenger,
               SocketId* id, int timeout_ms) {
    if (!IciBlockPool::initialized() || IciBlockPool::shm_name()[0] == '\0') {
        LOG(ERROR) << "IciConnect: IciBlockPool not initialized with a "
                      "shared region (call IciBlockPool::Init first)";
        errno = EINVAL;
        return -1;
    }
    // 1. Create the control segment (we are the client).
    char link_name[64];
    snprintf(link_name, sizeof(link_name), "/tpurpc_link_%d_%08lx",
             (int)getpid(), (unsigned long)fast_rand());
    int sfd = shm_open(link_name, O_CREAT | O_EXCL | O_RDWR, 0600);
    if (sfd < 0) {
        PLOG(ERROR) << "IciConnect: shm_open " << link_name;
        return -1;
    }
    if (ftruncate(sfd, (off_t)sizeof(ShmLinkCtrl)) != 0) {
        close(sfd);
        shm_unlink(link_name);
        return -1;
    }
    void* mem = mmap(nullptr, sizeof(ShmLinkCtrl), PROT_READ | PROT_WRITE,
                     MAP_SHARED, sfd, 0);
    close(sfd);
    if (mem == MAP_FAILED) {
        shm_unlink(link_name);
        return -1;
    }
    auto* ctrl = (ShmLinkCtrl*)mem;
    ctrl->version = 1;
    ctrl->c2s.InitPipe();
    ctrl->s2c.InitPipe();
    // Publish the initialized pipes before the magic the server validates.
    std::atomic_thread_fence(std::memory_order_release);
    ctrl->magic = ShmLinkCtrl::kMagic;

    auto fail = [&](const char* what) -> int {
        const int saved = errno;
        LOG(ERROR) << "IciConnect: " << what << ": " << strerror(saved);
        munmap(mem, sizeof(ShmLinkCtrl));
        shm_unlink(link_name);
        errno = saved;
        return -1;
    };

    // 2. TCP connect (the bootstrap/failure-detector connection).
    const int fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return fail("socket");
    timeval tv{timeout_ms / 1000, (timeout_ms % 1000) * 1000};
    setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
    setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    sockaddr_in addr;
    endpoint2sockaddr(server, &addr);
    if (::connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
        close(fd);
        return fail("connect");
    }

    // 3. Handshake: send our pool + link params, read the server's pool.
    HandshakeRequest req;
    memset(&req, 0, sizeof(req));
    memcpy(req.magic, "TICI", 4);
    req.version = shm_internal::kIciHandshakeVersion;
    snprintf(req.pool_name, sizeof(req.pool_name), "%s",
             IciBlockPool::shm_name());
    req.pool_size = IciBlockPool::shm_size();
    req.pool_epoch = IciBlockPool::pool_epoch();
    snprintf(req.link_name, sizeof(req.link_name), "%s", link_name);
    req.link_size = sizeof(ShmLinkCtrl);
    if (write_all_timeout(fd, &req, sizeof(req), timeout_ms) != 0) {
        close(fd);
        return fail("handshake send");
    }
    HandshakeResponse rsp;
    if (read_all_timeout(fd, &rsp, sizeof(rsp), timeout_ms) != 0) {
        close(fd);
        return fail("handshake recv");
    }
    if (memcmp(rsp.magic, "TICJ", 4) != 0) {
        close(fd);
        errno = TERR_RESPONSE;
        return fail("bad handshake response magic");
    }
    if (rsp.status != 0) {
        close(fd);
        errno = (int)rsp.status;
        return fail("server rejected handshake");
    }
    rsp.pool_name[sizeof(rsp.pool_name) - 1] = '\0';

    // 4. Map the server's registered memory (recording its announced
    //    pool generation for the stale-descriptor fence).
    PeerPool pp;
    if (shm_internal::AcquirePeerPool(rsp.pool_name, rsp.pool_size,
                                      rsp.pool_epoch, &pp) != 0) {
        close(fd);
        return fail("map server pool");
    }
    // Both sides have the control segment mapped now; drop the name.
    shm_unlink(link_name);

    // 5. Endpoint + socket: the TCP fd doubles as the socket's event fd.
    ShmIciEndpoint* ep =
        ShmIciEndpoint::Create(fd, mem, sizeof(ShmLinkCtrl),
                               /*is_client=*/true, rsp.pool_name, pp, server);
    SocketOptions opts;
    opts.fd = fd;
    opts.remote_side = server;
    opts.transport = ep;
    opts.owns_transport = true;
    opts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    opts.user = messenger;
    if (Socket::Create(opts, id) != 0) {
        // Ambiguous ownership on this can't-happen path: depending on
        // where Create failed, either it closed the fd (slot exhaustion)
        // or the recycling socket already Release()d the endpoint
        // (dispatcher failure). Releasing here could double-free either
        // one — leak the endpoint instead and say so.
        LOG(ERROR) << "IciConnect: Socket::Create failed after handshake; "
                      "leaking endpoint";
        return -1;
    }
    {
        // Descriptor scope: responses/requests on this connection may
        // reference exactly the server pool the handshake mapped.
        SocketUniquePtr created;
        if (Socket::AddressSocket(*id, &created) == 0) {
            created->set_peer_pool_id(
                pool_registry::IdFromName(rsp.pool_name));
        }
    }
    return 0;
}

// ---------------- server handshake protocol ----------------

namespace {

struct IciHandshakeMessage : public InputMessageBase {
    HandshakeRequest req;
};

ParseResult ParseIciHandshake(IOBuf* source, Socket* s, bool read_eof,
                              const void*) {
    (void)read_eof;
    char mag[4];
    const size_t have = source->size() < 4 ? source->size() : 4;
    source->copy_to(mag, have, 0);
    if (memcmp(mag, "TICI", have) != 0) {
        return ParseResult::make(ParseError::TRY_OTHERS);
    }
    if (s->transport() != nullptr) {
        // Already upgraded: "TICI" can only be payload of another protocol.
        return ParseResult::make(ParseError::TRY_OTHERS);
    }
    if (source->size() < sizeof(HandshakeRequest)) {
        return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
    }
    auto* msg = new IciHandshakeMessage;
    source->cutn(&msg->req, sizeof(msg->req));
    return ParseResult::make_ok(msg);
}

void ProcessIciHandshake(InputMessageBase* msg_base) {
    std::unique_ptr<IciHandshakeMessage> msg(
        (IciHandshakeMessage*)msg_base);
    SocketUniquePtr s = SocketUniquePtr::FromId(msg->socket_id);
    if (!s) return;
    HandshakeRequest& req = msg->req;
    req.pool_name[sizeof(req.pool_name) - 1] = '\0';
    req.link_name[sizeof(req.link_name) - 1] = '\0';

    HandshakeResponse rsp;
    memset(&rsp, 0, sizeof(rsp));
    memcpy(rsp.magic, "TICJ", 4);

    void* ctrl_mem = nullptr;
    bool pool_acquired = false;
    PeerPool pp{nullptr, 0};
    int err = 0;
    do {
        if (req.version != shm_internal::kIciHandshakeVersion ||
            req.link_size != sizeof(ShmLinkCtrl) ||
            !shm_internal::valid_shm_name(req.link_name)) {
            err = TERR_REQUEST;  // version/ABI mismatch or bad shm name
            break;
        }
        // Lazily give this process a registered pool if the server didn't.
        IciBlockPool::Init();
        if (IciBlockPool::shm_name()[0] == '\0') {
            err = ENOMEM;
            break;
        }
        // Map the client's control segment + registered memory.
        const int cfd = shm_open(req.link_name, O_RDWR, 0);
        if (cfd < 0) {
            err = errno != 0 ? errno : ENOENT;
            break;
        }
        struct stat st;
        if (fstat(cfd, &st) != 0 ||
            (size_t)st.st_size < sizeof(ShmLinkCtrl)) {
            close(cfd);
            err = TERR_REQUEST;
            break;
        }
        ctrl_mem = mmap(nullptr, sizeof(ShmLinkCtrl),
                        PROT_READ | PROT_WRITE, MAP_SHARED, cfd, 0);
        close(cfd);
        if (ctrl_mem == MAP_FAILED) {
            ctrl_mem = nullptr;
            err = errno != 0 ? errno : ENOMEM;
            break;
        }
        if (((ShmLinkCtrl*)ctrl_mem)->magic != ShmLinkCtrl::kMagic) {
            err = TERR_REQUEST;
            break;
        }
        std::atomic_thread_fence(std::memory_order_acquire);
        if (shm_internal::AcquirePeerPool(req.pool_name, req.pool_size,
                                          req.pool_epoch, &pp) != 0) {
            err = errno != 0 ? errno : ENOENT;
            break;
        }
        pool_acquired = true;
    } while (false);

    if (err != 0) {
        LOG(WARNING) << "ICI handshake from "
                     << endpoint2str(s->remote_side())
                     << " rejected: " << terror(err);
        if (ctrl_mem != nullptr) munmap(ctrl_mem, sizeof(ShmLinkCtrl));
        if (pool_acquired) shm_internal::ReleasePeerPool(req.pool_name);
        rsp.status = (uint32_t)err;
        write_all_timeout(s->fd(), &rsp, sizeof(rsp), 1000);
        s->SetFailedWithError(err);
        return;
    }

    // Install the data plane BEFORE replying: once the client sees the
    // response it may immediately post descriptors + doorbells, and those
    // doorbell bytes must be drained by Pump, not parsed as a protocol.
    ShmIciEndpoint* ep = ShmIciEndpoint::Create(
        s->fd(), ctrl_mem, sizeof(ShmLinkCtrl), /*is_client=*/false,
        req.pool_name, pp, s->remote_side());
    s->InstallTransport(ep);
    // Descriptor scope: this connection may reference exactly the pool
    // its handshake mapped.
    s->set_peer_pool_id(pool_registry::IdFromName(req.pool_name));
    snprintf(rsp.pool_name, sizeof(rsp.pool_name), "%s",
             IciBlockPool::shm_name());
    rsp.pool_size = IciBlockPool::shm_size();
    rsp.pool_epoch = IciBlockPool::pool_epoch();
    if (write_all_timeout(s->fd(), &rsp, sizeof(rsp), 1000) != 0) {
        s->SetFailedWithError(TERR_FAILED_SOCKET);
        return;
    }
    LOG(INFO) << "ICI link established with "
              << endpoint2str(s->remote_side()) << " (pool "
              << req.pool_name << ", " << req.pool_size << " bytes)";
}

int g_ici_hs_index = -1;

}  // namespace

void RegisterIciHandshakeProtocol() {
    if (g_ici_hs_index >= 0) return;
    Protocol p;
    p.parse = ParseIciHandshake;
    p.process = ProcessIciHandshake;
    p.name = "ici_handshake";
    g_ici_hs_index = RegisterProtocol(p);
}

int IciHandshakeProtocolIndex() { return g_ici_hs_index; }

}  // namespace tpurpc
