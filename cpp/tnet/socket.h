// Socket: the central connection object — versioned-id addressed, wait-free
// write queue, edge-triggered read dispatch.
//
// Modeled on reference src/brpc/socket.h:294 / socket.cpp:
//  - SocketId addressing + SetFailed/recycle via VersionedRefWithId
//  - write path: wait-free MPSC stack `_write_head` (socket.cpp:488,1695),
//    first writer writes inline once (socket.cpp:1615), leftovers go to a
//    KeepWrite fiber (socket.cpp:1800) batching via DoWrite (:1920);
//    back-pressure via EOVERCROWDED
//  - read path: OnInputEvent's atomic `_nevent` starts exactly one
//    processing fiber per readiness burst (socket.cpp:2229,2256)
//  - connect-on-first-write (ConnectIfNot socket.cpp:1409)
// The transport is pluggable: a TransportEndpoint (ICI/shm, see
// tnet/transport.h) can take over the data plane while this Socket keeps
// the id/lifecycle/queue semantics — the RdmaEndpoint pattern
// (reference src/brpc/rdma/rdma_endpoint.h).
#pragma once

#include <atomic>
#include <cstdint>
#include <deque>
#include <mutex>
#include <string>
#include <vector>

#include "tbase/endpoint.h"
#include "tbase/iobuf.h"
#include "tbase/time.h"
#include "tbase/versioned_ref.h"
#include "tnet/circuit_breaker.h"
#include "tnet/transport.h"
#include "tfiber/butex.h"
#include "tfiber/fiber.h"

namespace tpurpc {

class Socket;
using SocketId = VRefId;
using SocketUniquePtr = VRefPtr<Socket>;

struct SocketOptions {
    int fd = -1;  // may be -1: connect-on-first-write to remote_side
    EndPoint remote_side;
    // Edge-triggered readable callback (InputMessenger::OnNewMessages or
    // Acceptor::OnNewConnections). Runs on a fiber.
    void (*on_edge_triggered_events)(Socket*) = nullptr;
    void* user = nullptr;  // InputMessenger* / Acceptor* / Server*
    // Optional transport endpoint taking over the data plane (ICI).
    TransportEndpoint* transport = nullptr;
    // Registry tier of a plain-fd connection when it is NOT the default
    // tcp tier (ISSUE 14): a cross-pod peer's socket is created with
    // TierDcn() so descriptor eligibility, byte attribution and the
    // -dcn_emu_* shaping all key off the tier without a second data
    // plane. Ignored when `transport` is set (the endpoint knows its
    // own tier). -1 = default (tcp).
    int forced_transport_tier = -1;
    // If set, the socket Release()s the endpoint at recycle time (the
    // link frees itself once both sides' sockets are gone).
    bool owns_transport = false;
    // >0: on SetFailed, keep probing the remote every this-many ms and
    // Revive the SAME SocketId on success (reference
    // src/brpc/details/health_check.cpp — ids held by load balancers stay
    // valid across failures). 0 disables.
    int health_check_interval_ms = 0;
    // Client-side TLS: after connect, wrap the fd in a TLS transport
    // (tnet/tls.h) negotiating `tls_alpn` (e.g. "h2") with SNI
    // `tls_sni`. Requires libssl at runtime.
    bool tls = false;
    std::string tls_alpn;
    std::string tls_sni;
    // Invoked exactly once when the socket's last ref drops and the slot
    // recycles (reference SocketUser::BeforeRecycled). This is how an
    // Acceptor learns no event/processing fiber can still be touching a
    // connection — the quiesce signal Server teardown waits on. Must be
    // cheap and lock-light (runs on whatever fiber dropped the last ref).
    // Guarantee: if set, it fires even when Create() itself fails.
    void (*on_recycle)(void* arg, SocketId id) = nullptr;
    void* recycle_arg = nullptr;
};

class Socket : public VersionedRefWithId<Socket> {
public:
    // ---- creation / addressing ----
    static int Create(const SocketOptions& options, SocketId* id);
    static int AddressSocket(SocketId id, SocketUniquePtr* out) {
        out->reset();
        Socket* s = Address(id);
        if (s == nullptr) return -1;
        *out = SocketUniquePtr(s);
        return 0;
    }

    SocketId id() const { return vref_id(); }
    int fd() const { return fd_.load(std::memory_order_acquire); }
    const EndPoint& remote_side() const { return remote_side_; }
    const EndPoint& local_side() const { return local_side_; }
    void* user() const { return user_; }

    // ---- write path ----
    // Queue `data` (zero-copy moved) for ordered write. Returns 0, or -1
    // with errno (EOVERCROWDED when the unwritten backlog is too large,
    // or the socket is failed). Never blocks. `notify_id` (a CallId value)
    // is error-notified if the request is dropped by a write failure —
    // how in-flight RPCs learn their connection died (the reference passes
    // Controller ids through WriteRequest, socket.cpp Write w/ id_wait).
    // `enqueued_us`: the caller's stage-clock read as it enqueues the
    // frame (tvar/stage_recorder.h); the writer adds tnet.write_queue as
    // it posts the frame's last byte. 0 = not sampled.
    // `fail_after`: non-zero fails the socket with that error once this
    // request's last byte is posted -- a final reply the peer must read
    // before the connection goes (a rejected credential). What is queued
    // behind it is dropped.
    int Write(IOBuf* data, uint64_t notify_id = 0, int64_t enqueued_us = 0,
              int fail_after = 0);

    // ---- read path (called by EventDispatcher) ----
    static void OnInputEventById(SocketId id);
    static void OnOutputEventById(SocketId id);

    // ---- connect ----
    // Ensure connected (used by client sockets created with fd == -1);
    // blocks the calling fiber until connected or error. Returns 0 / -1.
    int ConnectIfNot();

    // ---- failure / health check ----
    int SetFailedWithError(int error_code);
    int error_code() const { return error_code_.load(std::memory_order_acquire); }
    // Process-wide failure observer, invoked once per socket from
    // OnFailed (the winning SetFailed). Lets upper layers react to
    // connection death without tnet depending on them (the RPC layer
    // cancels in-flight server calls here). The observer may run under
    // arbitrary locks — it must not run user code inline.
    using FailureObserver = void (*)(SocketId);
    static void set_failure_observer(FailureObserver ob);
    // Process-wide revive observer, invoked from ReviveAfterHealthCheck
    // after the socket is usable again (draining cleared, breaker reset).
    // Lets the outlier tier re-enter a revived-but-previously-ejected
    // backend through its probe ramp instead of at full weight: the
    // health probe only proves the process answers, not that it is fast.
    using ReviveObserver = void (*)(SocketId);
    static void set_revive_observer(ReviveObserver ob);
    // Stop the revive loop (set when the naming layer removes this server
    // for good; the health-check fiber then drops its ref and the socket
    // recycles).
    void StopHealthCheck() {
        hc_stop_.store(true, std::memory_order_release);
    }
    int health_check_interval_ms() const { return health_check_interval_ms_; }
    // Per-connection breaker (reference keeps one per Socket too); fed by
    // the client stack after each call, isolation = SetFailed + revive.
    CircuitBreaker& circuit_breaker() { return circuit_breaker_; }

    // ---- draining (zero-downtime lifecycle) ----
    // The peer announced a planned shutdown (tpu_std GOAWAY meta / h2
    // GOAWAY): the connection stays LIVE — in-flight calls complete
    // normally — but new calls must steer away (load balancers skip
    // draining nodes; pinned channels re-create their connection).
    // Cleared on slot reuse (Create) and on health-check revive: the
    // restarted process serves anew.
    void SetDraining() { draining_.store(true, std::memory_order_release); }
    bool Draining() const {
        return draining_.load(std::memory_order_acquire);
    }

    // Plugged data-plane transport (ICI), or null for the fd path.
    TransportEndpoint* transport() const { return transport_; }
    // The registry tier of this connection's data plane (tnet/transport.h):
    // TierTcp() for the plain-fd/TLS path, the endpoint's own tier
    // otherwise. Descriptor eligibility, credit accounting, and byte
    // attribution key off this — one seam, no per-transport special
    // cases.
    int transport_tier() const {
        if (transport_ != nullptr) return transport_->tier();
        return forced_tier_ >= 0 ? forced_tier_ : TierTcp();
    }
    // The raw SocketOptions::forced_transport_tier this socket was
    // created with (-1 = default tcp): the (endpoint, tier) key half the
    // SocketMap/SocketPool registries re-derive at Return/Remove time.
    int forced_transport_tier() const { return forced_tier_; }
    // Upgrade a live connection to a transport data plane (server side of
    // the ICI handshake). Must be called from the socket's input fiber
    // with no concurrent writers — i.e. before the peer can have sent any
    // post-handshake request (the handshake protocol guarantees this).
    // The socket takes ownership (Release()d at recycle).
    void InstallTransport(TransportEndpoint* t) {
        transport_ = t;
        owns_transport_ = true;
    }

    // ---- per-connection parsing state (owned by InputMessenger) ----
    IOPortal read_buf;
    int preferred_protocol_index = -1;
    // Zero-cut fast path (Protocol::peek): total bytes of the frame the
    // peeked header announced, 0 when no peek is outstanding. While set,
    // the messenger skips parse entirely until the whole frame arrived —
    // no re-peek, no re-parse per partial read. Input-fiber-only.
    int64_t pending_frame_bytes = 0;
    // Stage clock: when the read that began the bytes now at the front
    // of read_buf returned (input-fiber-owned, like read_buf).
    int64_t consumed_us = 0;
    // Protocol-private per-connection state (e.g. the HTTP/2 session:
    // HPACK context + stream table). Owned by the socket once set; the
    // deleter runs at recycle. Set from the input fiber only.
    void set_conn_data(void* data, void (*deleter)(void*)) {
        conn_data_ = data;
        conn_data_deleter_ = deleter;
    }
    void* conn_data() const { return conn_data_; }

    // ---- pipelined-response correlation ----
    // For protocols without correlation ids on the wire (redis,
    // memcache): each sender pushes {expected reply count, its CallId}
    // BEFORE writing, in write order; the response parser pops FIFO to
    // know whose replies it is reading (reference socket.h:532
    // PushPipelinedInfo / PopPipelinedInfo / GivebackPipelinedInfo).
    struct PipelinedInfo {
        uint32_t count = 0;    // replies this request expects
        uint64_t id_wait = 0;  // CallId to complete
    };
    void PushPipelinedInfo(const PipelinedInfo& pi) {
        std::lock_guard<std::mutex> g(pipeline_mu_);
        pipeline_q_.push_back(pi);
    }
    bool PopPipelinedInfo(PipelinedInfo* pi) {
        std::lock_guard<std::mutex> g(pipeline_mu_);
        if (pipeline_q_.empty()) return false;
        *pi = pipeline_q_.front();
        pipeline_q_.pop_front();
        return true;
    }
    // ---- auth fight (reference socket.h:515 FightAuthentication) ----
    // First caller on a fresh connection wins the right to attach the
    // credential; everyone else waits for its outcome. States: 0 none,
    // 1 in progress (one writer is authenticating), 2 done.
    // Returns: 0 = caller must attach the credential, 1 = already done.
    int FightAuthentication() {
        int expect = 0;
        if (auth_state_.compare_exchange_strong(
                expect, 1, std::memory_order_acq_rel)) {
            return 0;
        }
        return 1;
    }
    // Park until the in-flight authentication RESOLVES: done (state 2),
    // or aborted back to none (state 0 — the caller should re-fight).
    // Returns 0 on resolution, -1 on socket failure or timeout.
    int WaitAuthenticated(int64_t abstime_us);
    // The fight winner's call died without a processed response
    // (credential generation failed, timeout, retry): release the fight
    // so another caller can authenticate — otherwise the shared
    // connection wedges with every later call parked behind state 1.
    // No-op unless authentication is still in progress.
    void AbortAuthentication() {
        int expect = 1;
        if (auth_state_.compare_exchange_strong(
                expect, 0, std::memory_order_acq_rel)) {
            butex_word(auth_butex_)->fetch_add(1,
                                               std::memory_order_release);
            butex_wake_all(auth_butex_);
        }
    }
    // The authenticating call's response arrived: connection is trusted.
    // Exactly one caller transitions (via the transient publishing state
    // 3) and writes the user; races (e.g. two client response fibers)
    // collapse to the first winner.
    void SetAuthenticated(const std::string& user) {
        for (int from : {1, 0}) {
            int expect = from;
            if (auth_state_.compare_exchange_strong(
                    expect, 3, std::memory_order_acq_rel)) {
                auth_user_ = user;
                auth_state_.store(2, std::memory_order_release);
                butex_word(auth_butex_)->fetch_add(
                    1, std::memory_order_release);
                butex_wake_all(auth_butex_);
                return;
            }
        }
    }
    bool authenticated() const {
        return auth_state_.load(std::memory_order_acquire) == 2;
    }
    // Server side: the verified peer identity ("" before verification).
    const std::string& auth_user() const { return auth_user_; }

    // Un-push after a failed write (the entry must not shift correlation
    // for later callers). True if it was still queued.
    bool RemovePipelinedInfo(uint64_t id_wait) {
        std::lock_guard<std::mutex> g(pipeline_mu_);
        for (auto it = pipeline_q_.begin(); it != pipeline_q_.end(); ++it) {
            if (it->id_wait == id_wait) {
                pipeline_q_.erase(it);
                return true;
            }
        }
        return false;
    }
    // Fail every queued pipelined call (connection died) and clear.
    std::vector<PipelinedInfo> ResetPipelinedInfo() {
        std::lock_guard<std::mutex> g(pipeline_mu_);
        std::vector<PipelinedInfo> out(pipeline_q_.begin(),
                                       pipeline_q_.end());
        pipeline_q_.clear();
        return out;
    }

    // Bytes queued but not yet written (back-pressure signal).
    int64_t unwritten_bytes() const {
        return unwritten_bytes_.load(std::memory_order_relaxed);
    }

    // Requests queued and not yet retired by the writer; 0 means no
    // writer is elected (the single-writer election count).
    int64_t pending_writes() const {
        return write_pending_.load(std::memory_order_acquire);
    }

    // ---- per-socket stats (reference socket.h:127 SocketStat) ----
    void add_bytes_read(int64_t n) {
        bytes_read_.fetch_add(n, std::memory_order_relaxed);
        last_active_us_.store(monotonic_time_us(),
                              std::memory_order_relaxed);
    }
    void add_bytes_written(int64_t n) {
        bytes_written_.fetch_add(n, std::memory_order_relaxed);
        last_active_us_.store(monotonic_time_us(),
                              std::memory_order_relaxed);
    }
    int64_t bytes_read() const {
        return bytes_read_.load(std::memory_order_relaxed);
    }
    int64_t bytes_written() const {
        return bytes_written_.load(std::memory_order_relaxed);
    }
    // One-sided descriptor attribution (ISSUE 9): logical payload bytes
    // this connection delivered by REFERENCE (pool descriptors resolved
    // against a mapped peer pool) — they never crossed the fd/ring, so
    // bytes_read misses them, but they ARE this connection's data-plane
    // throughput. /connections adds them to the in-rate so the device
    // seam's GB/s is visible per connection.
    void add_descriptor_bytes_read(int64_t n) {
        descriptor_bytes_read_.fetch_add(n, std::memory_order_relaxed);
    }
    int64_t descriptor_bytes_read() const {
        return descriptor_bytes_read_.load(std::memory_order_relaxed);
    }
    // The ONE peer pool this connection's ICI handshake mapped (0 =
    // none). Descriptor resolution is bound to it: a request on this
    // connection may only reference the pool its handshake registered
    // (or, on an in-process link, this process's own pool) — a global
    // registry hit alone must never be enough, or any connection could
    // read any mapped tenant's pool memory.
    void set_peer_pool_id(uint64_t id) {
        peer_pool_id_.store(id, std::memory_order_relaxed);
    }
    uint64_t peer_pool_id() const {
        return peer_pool_id_.load(std::memory_order_relaxed);
    }
    int64_t created_us() const { return created_us_; }
    int64_t last_active_us() const {
        return last_active_us_.load(std::memory_order_relaxed);
    }

    // ---- per-connection I/O attribution (ISSUE 6; /connections) ----
    int64_t write_batches() const {
        return nwrite_batches_.load(std::memory_order_relaxed);
    }
    int64_t max_write_batch_bytes() const {
        return max_write_batch_.load(std::memory_order_relaxed);
    }
    int64_t queued_write_highwater() const {
        return queued_highwater_.load(std::memory_order_relaxed);
    }
    int64_t overcrowded_incidents() const {
        return novercrowded_.load(std::memory_order_relaxed);
    }
    // In/out bytes-per-second since the PREVIOUS call (or since creation
    // on the first): /connections computes scrape-to-scrape rates with
    // no per-socket sampler thread. Concurrent scrapes race benignly
    // (one of them sees a shorter window).
    struct IoRates {
        double in_bps = 0;
        double out_bps = 0;
    };
    IoRates ScrapeIoRates(int64_t now_us) {
        // Logical in-bytes: fd/ring bytes PLUS descriptor-referenced
        // bytes delivered in place (ISSUE 9) — the connection's true
        // data-plane rate.
        const int64_t in = bytes_read() + descriptor_bytes_read();
        const int64_t out = bytes_written();
        const int64_t prev_us = rate_scrape_us_.exchange(
            now_us, std::memory_order_relaxed);
        const int64_t prev_in =
            rate_scrape_in_.exchange(in, std::memory_order_relaxed);
        const int64_t prev_out =
            rate_scrape_out_.exchange(out, std::memory_order_relaxed);
        const int64_t base_us = prev_us != 0 ? prev_us : created_us_;
        const double dt = (double)(now_us - base_us) / 1e6;
        IoRates r;
        if (dt > 0) {
            r.in_bps = (double)(in - (prev_us != 0 ? prev_in : 0)) / dt;
            r.out_bps = (double)(out - (prev_us != 0 ? prev_out : 0)) / dt;
            if (r.in_bps < 0) r.in_bps = 0;    // slot-reuse race
            if (r.out_bps < 0) r.out_bps = 0;
        }
        return r;
    }

    // VersionedRefWithId hooks.
    void OnFailed();
    void OnRecycle();

private:
    friend class VersionedRefWithId<Socket>;
    friend class EventDispatcher;
    friend class WriteCoalesceScope;

    struct WriteRequest {
        std::atomic<WriteRequest*> next{nullptr};
        IOBuf data;
        uint64_t notify_id = 0;
        int64_t enqueued_us = 0;  // stage clock, see Write
        int fail_after = 0;       // see Write
        static WriteRequest* unlinked() { return (WriteRequest*)0x1; }
    };

    static void DropWriteRequest(WriteRequest* req);
    void CloseFdAndDropQueued();
    static void* HealthCheckThunk(void* arg);  // arg = Socket* (ref held)
    void HealthCheckLoop();
    // Reset connection state and un-fail (health-check fiber only, with
    // every other ref gone so no writer/reader is concurrent).
    int ReviveAfterHealthCheck();
    void StartKeepWriteIfNeeded();
    static void* KeepWriteThunk(void* arg);  // arg = SocketId
    void KeepWrite();
    // Drain pending write requests once; returns false on fatal error.
    bool FlushOnce(bool allow_block);
    // Drop every queued write request, error-notifying their CallIds. Only
    // the elected writer may call this (owns batch state). Needed at
    // failure time: recycle-time cleanup is too late for health-checked
    // sockets whose slot stays pinned while failed.
    void DrainWriteQueue();
    // Wait (fiber) until the fd is writable.
    int WaitEpollOut();
    static void* ProcessEventThunk(void* arg);  // arg = SocketId

    std::atomic<int> fd_{-1};
    EndPoint remote_side_;
    EndPoint local_side_;
    void (*on_edge_triggered_events_)(Socket*) = nullptr;
    void* user_ = nullptr;
    TransportEndpoint* transport_ = nullptr;
    bool owns_transport_ = false;
    int forced_tier_ = -1;  // SocketOptions::forced_transport_tier

    std::atomic<WriteRequest*> write_head_{nullptr};
    std::atomic<int64_t> write_pending_{0};
    std::atomic<int64_t> unwritten_bytes_{0};
    // In-progress batch owned by the single active writer. writer_consumed_
    // counts fully-written requests not yet subtracted from write_pending_;
    // it must survive the inline-flush -> KeepWrite handoff or the writer
    // election count drifts and the queue wedges.
    std::vector<WriteRequest*> inflight_batch_;
    size_t inflight_index_ = 0;
    int64_t writer_consumed_ = 0;

    std::atomic<int> nevent_{0};
    void* epollout_butex_ = nullptr;
    std::atomic<int> error_code_{0};
    std::atomic<bool> connecting_{false};
    void* connect_butex_ = nullptr;
    void* auth_butex_ = nullptr;
    std::atomic<int> auth_state_{0};
    std::string auth_user_;
    int health_check_interval_ms_ = 0;
    bool tls_ = false;
    std::string tls_alpn_;
    std::string tls_sni_;
    std::atomic<bool> hc_stop_{false};
    std::atomic<bool> draining_{false};
    CircuitBreaker circuit_breaker_;
    void (*on_recycle_)(void*, SocketId) = nullptr;
    void* recycle_arg_ = nullptr;
    std::atomic<int64_t> bytes_read_{0};
    std::atomic<int64_t> bytes_written_{0};
    std::atomic<int64_t> descriptor_bytes_read_{0};
    std::atomic<uint64_t> peer_pool_id_{0};
    int64_t created_us_ = 0;
    std::atomic<int64_t> last_active_us_{0};
    // I/O attribution (reset on slot reuse, like the byte counters).
    std::atomic<int64_t> nwrite_batches_{0};
    std::atomic<int64_t> max_write_batch_{0};
    std::atomic<int64_t> queued_highwater_{0};
    std::atomic<int64_t> novercrowded_{0};
    std::atomic<int64_t> rate_scrape_us_{0};
    std::atomic<int64_t> rate_scrape_in_{0};
    std::atomic<int64_t> rate_scrape_out_{0};
    void* conn_data_ = nullptr;
    void (*conn_data_deleter_)(void*) = nullptr;
    std::mutex pipeline_mu_;
    std::deque<PipelinedInfo> pipeline_q_;
};

// Process-wide count of write elections deferred into a coalescing scope
// (the rpc_socket_coalesced_writes tvar; /loops + tests read it here).
int64_t SocketCoalescedWrites();

// Write coalescing across one dispatch round (ISSUE 7): while a scope is
// armed on the current thread, a Socket::Write that wins the writer
// election DEFERS its flush — the request sits in the wait-free queue and
// the elected-writer role transfers to the scope. FlushDeferred() (called
// at the end of each messenger cut round, and by the scope destructor)
// then flushes each deferred socket once, so every response queued on the
// same connection during the round leaves in a single writev
// (rpc_socket_write_batch_bytes grows; rpc_socket_coalesced_writes counts
// deferred elections). Cross-request coalescing on pooled connections
// works the same way: the round's scope spans all sockets it wrote to.
//
// Safety: the scope is registered in a thread-local; TaskGroup::sched_park
// flushes-and-detaches it before any fiber switch, so a handler that
// (illegally, per the inline-safe contract) parks mid-round can never
// strand deferred writes on the old thread or leave a dangling pointer.
class WriteCoalesceScope {
public:
    WriteCoalesceScope();   // arms on this thread (no-op when nested)
    ~WriteCoalesceScope();  // FlushDeferred + disarm
    WriteCoalesceScope(const WriteCoalesceScope&) = delete;
    WriteCoalesceScope& operator=(const WriteCoalesceScope&) = delete;

    // Flush every deferred socket now; the scope stays armed for the
    // next round.
    void FlushDeferred();

    // Called by the elected writer in Socket::Write: true = the flush
    // was deferred into the active scope (a reference is held until the
    // flush). False when no scope is armed or it is full.
    static bool TryDefer(Socket* s);
    // sched_park hook: flush + detach whatever scope is armed on this
    // thread (the parking fiber may resume on another thread).
    static void FlushCurrent();

private:
    static constexpr int kMaxSockets = 8;
    Socket* sockets_[kMaxSockets];  // AddRef'd until flushed
    int nsockets_ = 0;
    bool armed_ = false;  // this instance owns the thread slot
};

}  // namespace tpurpc
