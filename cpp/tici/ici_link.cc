#include "tici/ici_link.h"

#include <sys/eventfd.h>
#include <unistd.h>

#include <cerrno>
#include <cstring>

#include "tbase/logging.h"
#include "tfiber/butex.h"

namespace tpurpc {

using ici_internal::Pipe;

// ---------------- link ----------------

IciLink::IciLink() {
    a_.link_ = this;
    b_.link_ = this;
    a_.out_ = &ab_;
    a_.in_ = &ba_;
    b_.out_ = &ba_;
    b_.in_ = &ab_;
    a_.peer_ = &b_;
    b_.peer_ = &a_;
    a_.evfd_ = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    b_.evfd_ = eventfd(0, EFD_NONBLOCK | EFD_CLOEXEC);
    a_.writable_butex_ = butex_create();
    b_.writable_butex_ = butex_create();
}

IciLink::~IciLink() {
    a_.Close();
    b_.Close();
    // Drain any refs still parked in the rings (posted but never
    // consumed): each producer frees its own unreleased descriptors.
    for (IciEndpoint* e : {&a_, &b_}) {
        Pipe* p = e->out_;
        const uint64_t head = p->head.load(std::memory_order_acquire);
        const uint64_t from = p->released.load(std::memory_order_acquire);
        for (uint64_t i = from; i < head; ++i) {
            p->ring[i % Pipe::kDepth].block->dec_ref();
        }
        p->released.store(head, std::memory_order_release);
    }
    if (a_.evfd_ >= 0) close(a_.evfd_);
    if (b_.evfd_ >= 0) close(b_.evfd_);
    butex_destroy(a_.writable_butex_);
    butex_destroy(b_.writable_butex_);
}

// ---------------- endpoint ----------------

bool IciEndpoint::Established() const {
    return !out_->closed.load(std::memory_order_acquire) &&
           !in_->closed.load(std::memory_order_acquire);
}

void IciEndpoint::ReleaseCompleted() {
    Pipe* p = out_;
    // Single claimer: the writer fiber and the pump fiber both call this
    // concurrently. The loser simply skips — the holder is about to free
    // the same range, and `released` (hence producer credits) only
    // advances AFTER the dec_refs are done, so no slot is reused while
    // its old block pointer is pending.
    bool expected = false;
    if (!p->releasing.compare_exchange_strong(expected, true,
                                              std::memory_order_acquire)) {
        return;
    }
    const uint64_t consumed = p->tail.load(std::memory_order_acquire);
    const uint64_t from = p->released.load(std::memory_order_relaxed);
    for (uint64_t i = from; i < consumed; ++i) {
        p->ring[i % Pipe::kDepth].block->dec_ref();
    }
    p->released.store(consumed, std::memory_order_release);
    p->releasing.store(false, std::memory_order_release);
}

ssize_t IciEndpoint::CutFromIOBufList(IOBuf* const* pieces, size_t count,
                                      int64_t*) {
    if (out_->closed.load(std::memory_order_acquire) ||
        in_->closed.load(std::memory_order_acquire)) {
        errno = EPIPE;
        return -1;
    }
    ReleaseCompleted();
    Pipe* p = out_;
    uint64_t head = p->head.load(std::memory_order_relaxed);
    // Reuse bounded by RELEASED slots (see Pipe::credits): slots in
    // [released, tail) still hold owned block pointers.
    const uint64_t limit =
        p->released.load(std::memory_order_acquire) + Pipe::kDepth;
    ssize_t posted = 0;
    size_t pending_bytes = 0;
    for (size_t i = 0; i < count; ++i) pending_bytes += pieces[i]->size();
    if (pending_bytes == 0) {
        return 0;  // all-empty pieces: match writev-on-empty so the
                   // caller's drop loop advances instead of livelocking
    }
    for (size_t i = 0; i < count && head < limit; ++i) {
        IOBuf* buf = pieces[i];
        while (head < limit && !buf->empty()) {
            IOBuf::BlockRef ref;
            if (!buf->cut_front_ref(&ref)) break;
            Pipe::Desc& d = p->ring[head % Pipe::kDepth];
            d.block = ref.block;  // ref ownership moves into the ring
            d.offset = ref.offset;
            d.length = ref.length;
            ++head;
            posted += ref.length;
        }
    }
    if (posted == 0) {
        errno = EAGAIN;  // real back-pressure: window full
        return -1;
    }
    p->head.store(head, std::memory_order_release);
    // Doorbell: suppressed unless the peer armed it (event suppression,
    // pillar 3). The arm flag for the peer's reads of this pipe lives on
    // the pipe itself.
    if (p->rx_armed.exchange(false, std::memory_order_acq_rel)) {
        uint64_t one = 1;
        ssize_t r = write(peer_->evfd_, &one, sizeof(one));
        (void)r;
        signals_sent_.fetch_add(1, std::memory_order_relaxed);
    }
    return posted;
}

int IciEndpoint::WaitWritable(int64_t abstime_us) {
    Pipe* p = out_;
    std::atomic<int>* word = butex_word(writable_butex_);
    const int expected = word->load(std::memory_order_acquire);
    // Tell the consumer to ring our doorbell when it consumes, then
    // re-check credits (the consume may have happened in between).
    p->tx_waiting.store(true, std::memory_order_release);
    // Fold already-consumed slots into `released` before the credit
    // re-check: credits() reads the producer-side `released` counter,
    // which only advances here — a consume that landed between the last
    // release pass and the tx_waiting store above produced no doorbell
    // (tx_waiting was still false), and without this the writer parks for
    // the whole wait despite free credits.
    ReleaseCompleted();
    if (p->credits() > 0 || p->closed.load(std::memory_order_acquire) ||
        in_->closed.load(std::memory_order_acquire)) {
        p->tx_waiting.store(false, std::memory_order_release);
        return 0;
    }
    butex_wait(writable_butex_, expected, &abstime_us);
    p->tx_waiting.store(false, std::memory_order_release);
    // Timeout is NOT fatal — same contract as the fd path's WaitEpollOut
    // (a server stalled past the wait window must not kill the link, it
    // just re-arms and waits again). Only a closed link is an error.
    return Established() ? 0 : -1;
}

ssize_t IciEndpoint::Pump(IOPortal* dst, PumpStamps*) {
    // Drain our doorbell so the edge re-arms at the eventfd level.
    uint64_t junk;
    while (read(evfd_, &junk, sizeof(junk)) > 0) {
    }
    // Send-side completions: free refs the peer consumed and wake any
    // writer parked on the window (waiters re-check credits, so a
    // spurious wake is harmless and cheaper than exact bookkeeping).
    ReleaseCompleted();
    butex_word(writable_butex_)->fetch_add(1, std::memory_order_release);
    butex_wake_all(writable_butex_);

    // Receive side: "DMA" pending descriptors into dst (pillar: the copy
    // happens once, at the target, like the interconnect engine).
    Pipe* p = in_;
    ssize_t received = 0;
    while (true) {
        uint64_t tail = p->tail.load(std::memory_order_relaxed);
        const uint64_t head = p->head.load(std::memory_order_acquire);
        if (tail == head) {
            if (p->closed.load(std::memory_order_acquire) && received == 0) {
                return 0;  // EOF
            }
            if (received > 0) return received;
            // Arm the doorbell, then re-check (a post may have raced the
            // arm; without the re-check it would be silently lost).
            p->rx_armed.store(true, std::memory_order_seq_cst);
            if (p->head.load(std::memory_order_seq_cst) != tail ||
                p->closed.load(std::memory_order_acquire)) {
                continue;
            }
            errno = EAGAIN;
            return -1;
        }
        while (tail != head) {
            const Pipe::Desc& d = p->ring[tail % Pipe::kDepth];
            // Zero-copy receive: same address space, so the "DMA" is a
            // reference — append_ref takes its own block ref (the
            // parser's cutn then moves pointers, never bytes). The
            // producer's ring ref releases independently via `released`;
            // disjoint byte ranges make concurrent tail-appends to a
            // shared TLS block benign. The cross-process shm link keeps
            // the copy (separate address spaces = a real transfer).
            dst->append_ref({d.offset, d.length, d.block});
            received += d.length;
            ++tail;
            p->tail.store(tail, std::memory_order_release);
        }
        // Consumed -> credits freed: ring the producer's doorbell if it
        // parked (piggybacked-ACK wakeup).
        if (p->tx_waiting.load(std::memory_order_acquire)) {
            uint64_t one = 1;
            ssize_t r = write(peer_->evfd_, &one, sizeof(one));
            (void)r;
            butex_word(peer_->writable_butex_)
                ->fetch_add(1, std::memory_order_release);
            butex_wake_all(peer_->writable_butex_);
        }
    }
}

void IciEndpoint::Release() {
    Close();
    link_->EndpointReleased();
}

void IciLink::EndpointReleased() {
    if (live_endpoints_.fetch_sub(1, std::memory_order_acq_rel) == 1) {
        delete this;
    }
}

void IciEndpoint::Close() {
    if (!out_->closed.exchange(true, std::memory_order_acq_rel)) {
        in_->closed.store(true, std::memory_order_release);
        // Wake the peer's pump (EOF) and any of our parked writers.
        uint64_t one = 1;
        ssize_t r = write(peer_->evfd_, &one, sizeof(one));
        (void)r;
        r = write(evfd_, &one, sizeof(one));
        (void)r;
        butex_word(writable_butex_)->fetch_add(1, std::memory_order_release);
        butex_wake_all(writable_butex_);
        butex_word(peer_->writable_butex_)
            ->fetch_add(1, std::memory_order_release);
        butex_wake_all(peer_->writable_butex_);
    }
}

}  // namespace tpurpc
