#include "tici/verbs.h"

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>
#include <set>
#include <vector>

#include "tbase/crc32c.h"
#include "tbase/errno.h"
#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tnet/fault_injection.h"
#include "tvar/reducer.h"

DEFINE_int64(verbs_lease_default_ms, 10000,
             "default lease span of a granted verb window when the "
             "grant request names none; the grantor's reaper frees the "
             "pin after this + -pool_lease_grace_ms");
DEFINE_int64(verbs_post_timeout_ms, 500,
             "per-attempt deadline of a posted verb: a post whose "
             "completion has not arrived (chaos verb_drop, lost wire "
             "frame, dead peer) is retried after this long");
DEFINE_int64(verbs_post_retries, 3,
             "attempts per posted verb before it completes "
             "TERR_RPC_TIMEDOUT");

namespace tpurpc {
namespace verbs {

namespace {

static LazyAdder g_posted("rpc_verbs_posted");
static LazyAdder g_completed("rpc_verbs_completed");
static LazyAdder g_bytes("rpc_verbs_bytes");
static LazyAdder g_stale("rpc_verbs_stale_rejects");
static LazyAdder g_parks("rpc_verbs_cq_parks");

// Initiator-side margin subtracted from a grant's lease span: a post
// inside the margin is refused locally, well before the grantor's
// reaper (deadline + grace) could free the pin under it.
constexpr int64_t kDeadlineMarginUs = 20 * 1000;

uint32_t CrcIOBuf(const IOBuf& b) {
    uint32_t crc = 0;
    for (size_t i = 0; i < b.backing_block_num(); ++i) {
        size_t len = 0;
        const char* d = b.backing_block_data(i, &len);
        crc = crc32c_extend(crc, d, len);
    }
    return crc;
}

// ---- grantor state ----

struct Window {
    uint64_t lease = 0;  // block_lease id (also the window_id)
    char* data = nullptr;
    uint64_t pool_off = 0;
    uint64_t len = 0;
    uint32_t mode = 0;
    uint64_t epoch = 0;  // pool epoch at grant
    uint64_t peer = 0;
};

// ---- initiator state ----

struct GrantWait {
    std::condition_variable cv;
    bool done = false;
    int status = TERR_RPC_TIMEDOUT;
    WindowInfo info;
    uint64_t sid = 0;
};

struct PendingWr {
    CompletionQueue* cq = nullptr;
    int op = 0;
    RemoteWindow w;
    uint64_t window_off = 0;
    std::vector<Sge> sgl;
    uint64_t total = 0;
    int64_t deadline_us = 0;  // this attempt's reap instant
    int attempts = 0;
};

// Writable remap of a peer pool for direct REMOTE_WRITE: the handshake
// mapping is PROT_READ, so the first write against a granted window
// re-opens the segment O_RDWR by name. Keyed by pool id; re-mapped
// when the registry epoch moved (owner restart = new segment bytes).
struct WritableMap {
    char* base = nullptr;
    size_t size = 0;
    uint64_t epoch = 0;
};

struct VerbsStateImpl {
    std::mutex mu;
    std::condition_variable cv;  // shared by GrantWait parks
    std::map<uint64_t, Window> windows;
    std::map<uint64_t, GrantWait*> grant_waits;  // token -> waiter
    std::map<uint64_t, PendingWr> pending;       // wr_id -> post
    std::map<uint64_t, WritableMap> writable;    // pool_id -> RW remap
    std::atomic<uint64_t> next_token{1};

    int (*grant_sender)(uint64_t, uint64_t, uint64_t, uint32_t,
                        int64_t) = nullptr;
    int (*wire_sender)(uint64_t, int, uint64_t, uint64_t, uint64_t,
                       uint64_t, uint64_t, uint32_t,
                       const IOBuf&) = nullptr;
    bool (*one_sided_probe)(uint64_t) = nullptr;
    uint32_t (*sgl_max_probe)(uint64_t) = nullptr;
};

// Immortal (same teardown rationale as the pool registry: completions
// may land from socket recycling during exit).
VerbsStateImpl& S() {
    static VerbsStateImpl* s = new VerbsStateImpl;
    return *s;
}

}  // namespace

// ---- completion queue ----

struct CompletionQueue::Impl {
    std::mutex mu;
    std::condition_variable cv;
    struct Entry {
        Completion c;
        int64_t ready_at_us = 0;  // chaos doorbell_delay holds it back
    };
    std::deque<Entry> q;
    // Bounded recent-wr_id memory absorbing duplicated wire
    // completions after the pending entry was already consumed.
    std::set<uint64_t> recent;
    std::deque<uint64_t> recent_order;
    bool shutdown = false;

    bool PushLocked(const Completion& c, int64_t ready_at) {
        if (recent.count(c.wr_id) != 0) return false;
        recent.insert(c.wr_id);
        recent_order.push_back(c.wr_id);
        while (recent_order.size() > 1024) {
            recent.erase(recent_order.front());
            recent_order.pop_front();
        }
        q.push_back(Entry{c, ready_at});
        return true;
    }

    bool TakeReadyLocked(int64_t now, Completion* out, int64_t* next) {
        *next = 0;
        for (auto it = q.begin(); it != q.end(); ++it) {
            if (it->ready_at_us <= now) {
                *out = it->c;
                q.erase(it);
                return true;
            }
            if (*next == 0 || it->ready_at_us < *next) {
                *next = it->ready_at_us;
            }
        }
        return false;
    }
};

CompletionQueue::CompletionQueue() : impl_(new Impl) {}
CompletionQueue::~CompletionQueue() { delete impl_; }

size_t CompletionQueue::depth() {
    std::lock_guard<std::mutex> g(impl_->mu);
    return impl_->q.size();
}

void CompletionQueue::Shutdown() {
    std::lock_guard<std::mutex> g(impl_->mu);
    impl_->shutdown = true;
    impl_->cv.notify_all();
}

void CompletionQueue::Push(const Completion& c, int64_t ready_at_us) {
    std::lock_guard<std::mutex> g(impl_->mu);
    if (impl_->PushLocked(c, ready_at_us)) impl_->cv.notify_all();
}

namespace {

// Deliver a completion into its CQ with exactly-once arbitration: the
// caller must already own the pending erase (or be an inline direct
// completion that never pended). Consults chaos kCqComplete — a
// delayed doorbell parks pollers instead of sleeping the deliverer.
void Deliver(CompletionQueue* cq, const Completion& c) {
    int64_t ready_at = 0;
    if (__builtin_expect(fault_injection_enabled(), 0)) {
        const FaultAction a = FaultInjection::Decide(
            FaultOp::kCqComplete, EndPoint(), (size_t)c.bytes);
        if (a.kind == FaultAction::kDelay) {
            ready_at = monotonic_time_us() + a.delay_us;
        }
    }
    *g_completed << 1;
    if (c.status == 0) *g_bytes << (int64_t)c.bytes;
    cq->Push(c, ready_at);
}

// Forward decl: Poll/Park drive the reaper.
void ReapPendingPosts(int64_t now);

int ExecutePending(uint64_t wr_id);

}  // namespace

bool CompletionQueue::Poll(Completion* out) {
    const int64_t now = monotonic_time_us();
    ReapPendingPosts(now);
    std::lock_guard<std::mutex> g(impl_->mu);
    int64_t next = 0;
    return impl_->TakeReadyLocked(now, out, &next);
}

bool CompletionQueue::Park(Completion* out, int64_t timeout_us) {
    const int64_t start = monotonic_time_us();
    const int64_t park_deadline =
        timeout_us < 0 ? 0 : start + timeout_us;
    bool counted = false;
    for (;;) {
        const int64_t now = monotonic_time_us();
        ReapPendingPosts(now);
        std::unique_lock<std::mutex> lk(impl_->mu);
        int64_t next_ready = 0;
        if (impl_->TakeReadyLocked(now, out, &next_ready)) return true;
        if (impl_->shutdown) return false;
        if (park_deadline != 0 && now >= park_deadline) return false;
        if (!counted) {
            *g_parks << 1;
            counted = true;
        }
        // Wake for: a push, the earliest delay-held entry maturing, the
        // park deadline, or the next pending-post reap tick — bounded
        // so a dropped verb's retry fires without a dedicated thread.
        int64_t wake = now + FLAGS_verbs_post_timeout_ms.get() * 1000;
        if (next_ready != 0 && next_ready < wake) wake = next_ready;
        if (park_deadline != 0 && park_deadline < wake) {
            wake = park_deadline;
        }
        impl_->cv.wait_for(lk, std::chrono::microseconds(wake - now));
    }
}

// ---- grantor side ----

int GrantWindow(uint64_t peer_key, uint64_t length, uint32_t mode,
                int64_t lease_ms, WindowInfo* out) {
    if (length == 0 || out == nullptr ||
        (mode & (kWinRead | kWinWrite)) == 0) {
        return TERR_REQUEST;
    }
    IOBuf buf;
    char* data = nullptr;
    if (!IciBlockPool::AllocatePoolAttachment((size_t)length, &buf,
                                              &data)) {
        return TERR_OVERLOAD;  // pool dry / length above slab classes
    }
    uint64_t off = 0;
    if (!IciBlockPool::OffsetOf(data, &off)) {
        return TERR_OVERLOAD;
    }
    if (lease_ms <= 0) lease_ms = FLAGS_verbs_lease_default_ms.get();
    const uint64_t lease = block_lease::Pin(std::move(buf), "win");
    const int64_t deadline = monotonic_time_us() + lease_ms * 1000;
    // The arm is the liveness registration: the reaper and peer-death
    // reclamation free the pin through the SAME lease machinery the
    // descriptor plane uses (call id = window id for the ledger).
    // This plane's "loopback, no link" peer is 0; the lease registry's is
    // kNoPeer (0 is a real SocketId there).
    block_lease::Arm(lease, lease, deadline,
                     peer_key != 0 ? peer_key : block_lease::kNoPeer);
    VerbsStateImpl& s = S();
    Window w;
    w.lease = lease;
    w.data = data;
    w.pool_off = off;
    w.len = length;
    w.mode = mode;
    w.epoch = IciBlockPool::pool_epoch();
    w.peer = peer_key;
    {
        std::lock_guard<std::mutex> g(s.mu);
        s.windows[lease] = w;
    }
    out->window_id = lease;
    out->pool_id = IciBlockPool::pool_id();
    out->offset = off;
    out->length = length;
    out->epoch = w.epoch;
    out->mode = mode;
    out->lease_ms = lease_ms;
    return 0;
}

bool CloseWindow(uint64_t window_id) {
    VerbsStateImpl& s = S();
    uint64_t lease = 0;
    {
        std::lock_guard<std::mutex> g(s.mu);
        auto it = s.windows.find(window_id);
        if (it == s.windows.end()) return false;
        lease = it->second.lease;
        s.windows.erase(it);
    }
    block_lease::Release(lease);
    return true;
}

int WindowPtr(uint64_t window_id, uint64_t offset, uint64_t len,
              uint64_t wire_epoch, uint32_t need, char** ptr) {
    VerbsStateImpl& s = S();
    std::lock_guard<std::mutex> g(s.mu);
    auto it = s.windows.find(window_id);
    if (it == s.windows.end()) {
        *g_stale << 1;  // reclaimed/unknown: never recycled bytes
        return TERR_STALE_EPOCH;
    }
    Window& w = it->second;
    if (!block_lease::Alive(w.lease)) {
        // The reaper or peer-death sweep beat us: the slab may already
        // be recycled into another call's payload.
        s.windows.erase(it);
        *g_stale << 1;
        return TERR_STALE_EPOCH;
    }
    if (wire_epoch != w.epoch ||
        w.epoch != IciBlockPool::pool_epoch()) {
        *g_stale << 1;
        return TERR_STALE_EPOCH;
    }
    if ((w.mode & need) != need) return TERR_REQUEST;
    if (len == 0 || offset > w.len || len > w.len - offset) {
        return TERR_REQUEST;
    }
    if (ptr != nullptr) *ptr = w.data + offset;
    return 0;
}

// ---- initiator helpers ----

namespace {

uint64_t SglTotal(const Sge* sgl, uint32_t nsge) {
    uint64_t t = 0;
    for (uint32_t i = 0; i < nsge; ++i) {
        if (sgl[i].addr == nullptr || sgl[i].len == 0) return 0;
        t += sgl[i].len;
    }
    return t;
}

// Resolve the window's pool for DIRECT access. Returns the span base
// (already offset to the window) or null; *stale set when the mapping
// exists but its generation moved (the caller completes
// TERR_STALE_EPOCH instead of degrading to the wire).
char* DirectBase(const RemoteWindow& w, bool writable, bool* stale) {
    *stale = false;
    const char* base = nullptr;
    size_t size = 0;
    uint64_t ep = 0;
    if (!pool_registry::Resolve(w.pool_id, &base, &size, &ep)) {
        return nullptr;
    }
    if (ep != w.epoch) {
        *stale = true;
        return nullptr;
    }
    if (w.offset + w.length > size) {
        *stale = true;
        return nullptr;
    }
    if (!writable) return const_cast<char*>(base) + w.offset;
    // Writes against our OWN pool use the Init-time RW mapping.
    if (w.pool_id == IciBlockPool::pool_id()) {
        return IciBlockPool::shm_base() + w.offset;
    }
    // Peer pool: the handshake mapping is PROT_READ — re-open the
    // segment O_RDWR by name (the grant is the authorization; same-
    // user shm). Cached per pool, invalidated when the registry epoch
    // moves (owner restart = different segment bytes).
    VerbsStateImpl& s = S();
    std::lock_guard<std::mutex> g(s.mu);
    auto it = s.writable.find(w.pool_id);
    if (it != s.writable.end() && it->second.epoch == ep &&
        it->second.size >= w.offset + w.length) {
        return it->second.base + w.offset;
    }
    char name[128];
    if (!pool_registry::NameOf(w.pool_id, name, sizeof(name))) {
        return nullptr;
    }
    const int fd = shm_open(name, O_RDWR, 0);
    if (fd < 0) return nullptr;
    struct stat st;
    if (fstat(fd, &st) != 0 || (size_t)st.st_size < size) {
        close(fd);
        return nullptr;
    }
    void* mem =
        mmap(nullptr, size, PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
    close(fd);
    if (mem == MAP_FAILED) return nullptr;
    if (it != s.writable.end()) {
        munmap(it->second.base, it->second.size);
        s.writable.erase(it);
    }
    s.writable[w.pool_id] = WritableMap{(char*)mem, size, ep};
    return (char*)mem + w.offset;
}

bool DirectAllowed(const RemoteWindow& w) {
    VerbsStateImpl& s = S();
    bool (*probe)(uint64_t) = s.one_sided_probe;
    // Loopback grants (peer 0: in-process tests, local lanes) always
    // may touch the local mapping; real links defer to the transport
    // tier's one_sided bit when the policy registered the probe.
    if (w.peer == 0) return true;
    if (probe != nullptr) return probe(w.peer);
    return true;
}

// Finish wr_id with `status` if still pending: erase-then-deliver (the
// erase is the exactly-once arbitration point). `payload` scatters
// into the READ sgl on success.
void CompletePending(uint64_t wr_id, int status, const IOBuf* payload) {
    VerbsStateImpl& s = S();
    PendingWr e;
    {
        std::lock_guard<std::mutex> g(s.mu);
        auto it = s.pending.find(wr_id);
        if (it == s.pending.end()) return;  // lost the race: delivered
        e = std::move(it->second);
        s.pending.erase(it);
    }
    if (status == 0 && e.op == kRemoteRead && payload != nullptr) {
        size_t pos = 0;
        for (const Sge& sg : e.sgl) {
            payload->copy_to(sg.addr, (size_t)sg.len, pos);
            pos += (size_t)sg.len;
        }
    }
    Completion c;
    c.wr_id = wr_id;
    c.status = status;
    c.bytes = status == 0 ? e.total : 0;
    c.op = e.op;
    flight::Record(flight::kVerbComplete, wr_id, (uint64_t)(uint32_t)status);
    Deliver(e.cq, c);
}

// One attempt of a pending post against a SNAPSHOT of the entry (no
// lock held: the memcpy/wire send must not serialize every post):
// direct memcpy when the tier allows and the mapping is current, else
// the emulated wire path. Chaos kVerbPost may make the attempt vanish
// (the per-attempt deadline retries it). Returns 1 when in flight on
// the wire; 0 otherwise, with *terminal_status >= 0 when the attempt
// reached a verdict.
int ExecuteAttempt(PendingWr* e, uint64_t wr_id, int* terminal_status) {
    const int64_t now = monotonic_time_us();
    if (e->w.deadline_us != 0 &&
        now > e->w.deadline_us - kDeadlineMarginUs) {
        *g_stale << 1;
        *terminal_status = TERR_STALE_EPOCH;
        return 0;
    }
    if (__builtin_expect(fault_injection_enabled(), 0)) {
        const FaultAction a = FaultInjection::Decide(
            FaultOp::kVerbPost, EndPoint(), (size_t)e->total);
        if (a.kind == FaultAction::kDrop) {
            // The post vanishes in flight: no completion will arrive;
            // the per-attempt deadline reaps and retries it.
            return 0;
        }
    }
    if (DirectAllowed(e->w)) {
        bool stale = false;
        const bool writable = e->op == kRemoteWrite;
        char* base = DirectBase(e->w, writable, &stale);
        if (stale) {
            *g_stale << 1;
            *terminal_status = TERR_STALE_EPOCH;
            return 0;
        }
        if (base != nullptr) {
            char* p = base + e->window_off;
            if (e->op == kRemoteWrite) {
                for (const Sge& sg : e->sgl) {
                    memcpy(p, sg.addr, (size_t)sg.len);
                    p += sg.len;
                }
            } else {
                for (const Sge& sg : e->sgl) {
                    memcpy(sg.addr, p, (size_t)sg.len);
                    p += sg.len;
                }
            }
            *terminal_status = 0;
            return 0;
        }
        // Pool not mapped here (or RW remap failed): fall through to
        // the wire emulation — same verbs, two-sided underneath.
    }
    VerbsStateImpl& s = S();
    int (*sender)(uint64_t, int, uint64_t, uint64_t, uint64_t, uint64_t,
                  uint64_t, uint32_t, const IOBuf&) = s.wire_sender;
    if (sender == nullptr || e->w.peer == 0) {
        *terminal_status = TERR_INTERNAL;
        return 0;
    }
    IOBuf payload;
    uint32_t crc = 0;
    if (e->op == kRemoteWrite) {
        for (const Sge& sg : e->sgl) {
            payload.append(sg.addr, (size_t)sg.len);
            crc = crc32c_extend(crc, sg.addr, (size_t)sg.len);
        }
    }
    if (sender(e->w.peer, e->op, wr_id, e->w.window_id, e->window_off,
               e->total, e->w.epoch, crc, payload) != 0) {
        *terminal_status = TERR_FAILED_SOCKET;
        return 0;
    }
    return 1;  // in flight: completion (or the reaper) finishes it
}

int ExecutePending(uint64_t wr_id) {
    VerbsStateImpl& s = S();
    PendingWr snapshot;
    {
        std::lock_guard<std::mutex> g(s.mu);
        auto it = s.pending.find(wr_id);
        if (it == s.pending.end()) return 0;
        it->second.attempts++;
        it->second.deadline_us =
            monotonic_time_us() +
            FLAGS_verbs_post_timeout_ms.get() * 1000;
        snapshot = it->second;
    }
    int terminal = -1;
    const int r = ExecuteAttempt(&snapshot, wr_id, &terminal);
    if (r == 0 && terminal >= 0) CompletePending(wr_id, terminal, nullptr);
    return 0;
}

void ReapPendingPosts(int64_t now) {
    VerbsStateImpl& s = S();
    std::vector<uint64_t> retry, timed_out;
    {
        std::lock_guard<std::mutex> g(s.mu);
        for (auto& kv : s.pending) {
            if (kv.second.deadline_us > now) continue;
            if (kv.second.attempts >=
                (int)FLAGS_verbs_post_retries.get()) {
                timed_out.push_back(kv.first);
            } else {
                retry.push_back(kv.first);
            }
        }
    }
    for (uint64_t id : timed_out) {
        flight::Record(flight::kVerbReap, id, (uint64_t)TERR_RPC_TIMEDOUT);
        CompletePending(id, TERR_RPC_TIMEDOUT, nullptr);
    }
    for (uint64_t id : retry) ExecutePending(id);
}

int Post(CompletionQueue* cq, int op, uint64_t wr_id,
         const RemoteWindow& w, uint64_t window_off, const Sge* sgl,
         uint32_t nsge) {
    if (cq == nullptr || sgl == nullptr || nsge == 0 ||
        w.window_id == 0) {
        return TERR_REQUEST;
    }
    VerbsStateImpl& s = S();
    uint32_t sgl_max = kDefaultSglMax;
    if (s.sgl_max_probe != nullptr && w.peer != 0) {
        const uint32_t m = s.sgl_max_probe(w.peer);
        if (m != 0) sgl_max = m;
    }
    if (nsge > sgl_max) return TERR_REQUEST;
    const uint64_t total = SglTotal(sgl, nsge);
    if (total == 0 || window_off > w.length ||
        total > w.length - window_off) {
        return TERR_REQUEST;
    }
    const uint32_t need = op == kRemoteWrite ? kWinWrite : kWinRead;
    if ((w.mode & need) != need) return TERR_REQUEST;
    PendingWr e;
    e.cq = cq;
    e.op = op;
    e.w = w;
    e.window_off = window_off;
    e.sgl.assign(sgl, sgl + nsge);
    e.total = total;
    e.attempts = 0;
    e.deadline_us =
        monotonic_time_us() + FLAGS_verbs_post_timeout_ms.get() * 1000;
    {
        std::lock_guard<std::mutex> g(s.mu);
        if (s.pending.count(wr_id) != 0) return TERR_REQUEST;
        s.pending[wr_id] = e;
    }
    *g_posted << 1;
    flight::Record(flight::kVerbPost, wr_id,
                   ((uint64_t)(uint32_t)op << 32) | (total & 0xffffffffu));
    ExecutePending(wr_id);
    return 0;
}

}  // namespace

int PostRead(CompletionQueue* cq, uint64_t wr_id, const RemoteWindow& w,
             uint64_t window_off, Sge* sgl, uint32_t nsge) {
    return Post(cq, kRemoteRead, wr_id, w, window_off, sgl, nsge);
}

int PostWrite(CompletionQueue* cq, uint64_t wr_id, const RemoteWindow& w,
              uint64_t window_off, const Sge* sgl, uint32_t nsge) {
    return Post(cq, kRemoteWrite, wr_id, w, window_off, sgl, nsge);
}

// ---- grant exchange ----

void SetGrantRequestSender(int (*fn)(uint64_t, uint64_t, uint64_t,
                                     uint32_t, int64_t)) {
    S().grant_sender = fn;
}
void SetVerbWireSender(int (*fn)(uint64_t, int, uint64_t, uint64_t,
                                 uint64_t, uint64_t, uint64_t, uint32_t,
                                 const IOBuf&)) {
    S().wire_sender = fn;
}
void SetOneSidedProbe(bool (*fn)(uint64_t)) { S().one_sided_probe = fn; }
void SetSglMaxProbe(uint32_t (*fn)(uint64_t)) { S().sgl_max_probe = fn; }

int RequestWindow(uint64_t sid, uint64_t length, uint32_t mode,
                  int64_t timeout_ms, RemoteWindow* out) {
    if (out == nullptr || length == 0) return TERR_REQUEST;
    VerbsStateImpl& s = S();
    int (*sender)(uint64_t, uint64_t, uint64_t, uint32_t, int64_t) =
        s.grant_sender;
    if (sender == nullptr) return TERR_INTERNAL;
    const uint64_t token =
        s.next_token.fetch_add(1, std::memory_order_relaxed);
    GrantWait wait;
    wait.sid = sid;
    {
        std::lock_guard<std::mutex> g(s.mu);
        s.grant_waits[token] = &wait;
    }
    const int64_t lease_ms = FLAGS_verbs_lease_default_ms.get();
    if (sender(sid, token, length, mode, lease_ms) != 0) {
        std::lock_guard<std::mutex> g(s.mu);
        s.grant_waits.erase(token);
        return TERR_FAILED_SOCKET;
    }
    int status;
    WindowInfo info;
    {
        std::unique_lock<std::mutex> lk(s.mu);
        if (timeout_ms <= 0) timeout_ms = 1000;
        wait.cv.wait_for(lk, std::chrono::milliseconds(timeout_ms),
                         [&wait] { return wait.done; });
        status = wait.done ? wait.status : TERR_RPC_TIMEDOUT;
        info = wait.info;
        s.grant_waits.erase(token);
    }
    if (status != 0) return status;
    out->window_id = info.window_id;
    out->pool_id = info.pool_id;
    out->offset = info.offset;
    out->length = info.length;
    out->epoch = info.epoch;
    out->mode = info.mode;
    out->peer = sid;
    out->deadline_us = monotonic_time_us() + info.lease_ms * 1000;
    return 0;
}

int HandleGrantRequest(uint64_t sid, uint64_t length, uint32_t mode,
                       int64_t lease_ms, WindowInfo* out) {
    return GrantWindow(sid, length, mode, lease_ms, out);
}

void HandleGrantResponse(uint64_t token, int status,
                         const WindowInfo& info) {
    VerbsStateImpl& s = S();
    std::lock_guard<std::mutex> g(s.mu);
    auto it = s.grant_waits.find(token);
    if (it == s.grant_waits.end()) return;  // waiter timed out already
    it->second->done = true;
    it->second->status = status;
    it->second->info = info;
    it->second->cv.notify_all();
}

int HandleWireVerb(int op, uint64_t wr_id, uint64_t window_id,
                   uint64_t offset, uint64_t len, uint64_t epoch,
                   uint32_t crc, const IOBuf& payload, IOBuf* out,
                   uint32_t* out_crc) {
    // Grantor-side wire event: the initiator's kVerbPost for this wr_id
    // pairs with this record in the merged cross-node timeline.
    flight::Record(flight::kVerbWire, wr_id,
                   ((uint64_t)(uint32_t)op << 32) | (len & 0xffffffffu));
    // The wire-verb resolve seam inherits the chaos pool_stale kind (the
    // same fence the descriptor resolve path injects): answer the
    // retriable stale error without touching window state, so the soak
    // proves initiators survive a fenced grantor.
    if (__builtin_expect(fault_injection_enabled(), 0)) {
        const FaultAction a = FaultInjection::Decide(
            FaultOp::kPoolResolve, EndPoint(), (size_t)len);
        if (a.kind == FaultAction::kStaleEpoch) {
            *g_stale << 1;
            return TERR_STALE_EPOCH;
        }
    }
    const uint32_t need = op == kRemoteWrite ? kWinWrite : kWinRead;
    char* p = nullptr;
    const int rc = WindowPtr(window_id, offset, len, epoch, need, &p);
    if (rc != 0) return rc;
    if (op == kRemoteWrite) {
        if (payload.size() != len) return TERR_REQUEST;
        if (CrcIOBuf(payload) != crc) return TERR_REQUEST;
        payload.copy_to(p, (size_t)len);
        return 0;
    }
    if (op != kRemoteRead || out == nullptr) return TERR_REQUEST;
    out->append(p, (size_t)len);
    if (out_crc != nullptr) *out_crc = crc32c_extend(0, p, (size_t)len);
    return 0;
}

void HandleWireCompletion(uint64_t wr_id, int status,
                          const IOBuf& payload, uint32_t crc) {
    if (status == 0 && !payload.empty() && CrcIOBuf(payload) != crc) {
        // Bytes damaged in flight: fail the post retriable.
        CompletePending(wr_id, TERR_REQUEST, nullptr);
        return;
    }
    CompletePending(wr_id, status, &payload);
}

void OnPeerDead(uint64_t peer_key) {
    if (peer_key == 0) return;
    VerbsStateImpl& s = S();
    std::vector<uint64_t> leases, posts;
    {
        std::lock_guard<std::mutex> g(s.mu);
        for (auto it = s.windows.begin(); it != s.windows.end();) {
            if (it->second.peer == peer_key) {
                leases.push_back(it->second.lease);
                it = s.windows.erase(it);
            } else {
                ++it;
            }
        }
        for (auto& kv : s.pending) {
            if (kv.second.w.peer == peer_key) posts.push_back(kv.first);
        }
        for (auto& kv : s.grant_waits) {
            if (kv.second->sid == peer_key && !kv.second->done) {
                kv.second->done = true;
                kv.second->status = TERR_FAILED_SOCKET;
                kv.second->cv.notify_all();
            }
        }
    }
    // block_lease::ReleasePeer (the caller's sibling sweep) may race
    // these releases — Release is exactly-once, both orders are safe.
    for (uint64_t l : leases) block_lease::Release(l);
    for (uint64_t id : posts) {
        CompletePending(id, TERR_FAILED_SOCKET, nullptr);
    }
}

// ---- observability ----

void ExposeVars() {
    *g_posted << 0;
    *g_completed << 0;
    *g_bytes << 0;
    *g_stale << 0;
    *g_parks << 0;
}

int64_t posted() { return (*g_posted).get_value(); }
int64_t completed() { return (*g_completed).get_value(); }
int64_t bytes_moved() { return (*g_bytes).get_value(); }
int64_t stale_rejects() { return (*g_stale).get_value(); }
int64_t cq_parks() { return (*g_parks).get_value(); }

size_t window_count() {
    VerbsStateImpl& s = S();
    std::lock_guard<std::mutex> g(s.mu);
    return s.windows.size();
}

size_t pending_posts() {
    VerbsStateImpl& s = S();
    std::lock_guard<std::mutex> g(s.mu);
    return s.pending.size();
}

std::string DebugString() {
    VerbsStateImpl& s = S();
    std::string out;
    char line[192];
    snprintf(line, sizeof(line),
             "verbs posted=%lld completed=%lld bytes=%lld "
             "stale_rejects=%lld cq_parks=%lld pending=%zu\n",
             (long long)posted(), (long long)completed(),
             (long long)bytes_moved(), (long long)stale_rejects(),
             (long long)cq_parks(), pending_posts());
    out += line;
    std::lock_guard<std::mutex> g(s.mu);
    size_t shown = 0;
    for (const auto& kv : s.windows) {
        if (++shown > 64) break;
        snprintf(line, sizeof(line),
                 "window %llu len=%llu mode=%u peer=%llu epoch=%llu\n",
                 (unsigned long long)kv.first,
                 (unsigned long long)kv.second.len, kv.second.mode,
                 (unsigned long long)kv.second.peer,
                 (unsigned long long)kv.second.epoch);
        out += line;
    }
    return out;
}

}  // namespace verbs
}  // namespace tpurpc
