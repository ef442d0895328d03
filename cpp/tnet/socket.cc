#include "tnet/socket.h"

#include <fcntl.h>
#include <netinet/in.h>
#include <poll.h>
#include <netinet/tcp.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cstring>
#include <string>

#include "tbase/errno.h"
#include "tbase/flags.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tfiber/call_id.h"
#include "tfiber/task_group.h"
#include "tnet/event_dispatcher.h"
#include "tnet/fault_injection.h"
#include "tnet/tls.h"
#include "tnet/transport.h"
#include "tvar/latency_recorder.h"
#include "tvar/reducer.h"
#include "tvar/stage_recorder.h"

DEFINE_int64(socket_max_unwritten_bytes, 64 * 1024 * 1024,
             "write backlog limit before EOVERCROWDED back-pressure");
// -1 keeps kernel autotuning (the right default: pinning a size disables
// both shrinking of idle connections and growth on high-BDP links).
// Benchmarks with windowed large messages set these explicitly.
DEFINE_int32(socket_send_buffer_size, -1,
             "SO_SNDBUF per connection; -1 = kernel autotune");
DEFINE_int32(socket_recv_buffer_size, -1,
             "SO_RCVBUF per connection; -1 = kernel autotune");
// Reference details/health_check.cpp:51-107 OnAppHealthCheckDone: beyond
// the TCP connect probe, require an APPLICATION-level answer before
// reviving an isolated server (a listening-but-broken process must stay
// isolated). Empty disables; servers in this framework always serve
// /health on their RPC port.
DEFINE_string(health_check_path, "",
              "HTTP path probed (expects 200) before reviving a failed "
              "server; empty = TCP connect probe only");

namespace tpurpc {

// Health-check revivals, observable in /vars and /metrics (the mesh
// chaos soak asserts on it).
static LazyAdder g_hc_revives("rpc_health_check_revives");

// Process-wide I/O attribution families (ISSUE 6): writev batch sizes
// as a real summary (small batches at high QPS = the write-coalescing
// opportunity of ROADMAP item 4), EOVERCROWDED incidents, and the
// biggest write backlog any connection reached. Per-connection views
// live on /connections.
static LazyAdder g_eovercrowded("rpc_socket_eovercrowded");
// Safety-net pair of the KeepWrite fiber's EPOLLOUT wait (see
// WaitEpollOut): cumulative /vars integers.
static LazyAdder g_epollout_timeouts("rpc_socket_epollout_timeouts");
static LazyAdder g_epollout_timeouts_found_work(
    "rpc_socket_epollout_timeouts_found_work");

static LatencyRecorder* write_batch_recorder() {
    static LatencyRecorder* r = [] {
        auto* x = new LatencyRecorder;
        x->expose("rpc_socket_write_batch_bytes");
        return x;
    }();
    return r;
}

static IntCell* queued_write_highwater_cell() {
    static IntCell* c = [] {
        auto* x = new IntCell;
        x->expose("rpc_socket_queued_write_highwater");
        return x;
    }();
    return c;
}

static int make_non_blocking(int fd) {
    const int flags = fcntl(fd, F_GETFL, 0);
    if (flags < 0) return -1;
    return fcntl(fd, F_SETFL, flags | O_NONBLOCK);
}

static void ApplySocketBufferSizes(int fd) {
    const int snd = FLAGS_socket_send_buffer_size.get();
    if (snd > 0) setsockopt(fd, SOL_SOCKET, SO_SNDBUF, &snd, sizeof(snd));
    const int rcv = FLAGS_socket_recv_buffer_size.get();
    if (rcv > 0) setsockopt(fd, SOL_SOCKET, SO_RCVBUF, &rcv, sizeof(rcv));
}

// ---------------- creation / recycle ----------------

// Takes ownership of options.fd: on ANY failure path the fd is closed here
// (callers must not close it again — fd numbers recycle fast under load and
// a double close can kill an unrelated connection).
int Socket::Create(const SocketOptions& options, SocketId* id) {
    Socket* s = nullptr;
    if (VersionedRefWithId<Socket>::Create(id, &s) != 0) {
        if (options.transport != nullptr && options.owns_transport) {
            options.transport->Release();  // a TLS transport owns the fd
        } else if (options.fd >= 0) {
            close(options.fd);
        }
        // Keep the fires-exactly-once contract even when no slot was ever
        // allocated (callers pre-account and rely on the callback to undo).
        if (options.on_recycle != nullptr) {
            options.on_recycle(options.recycle_arg, INVALID_VREF_ID);
        }
        return -1;
    }
    // Slots are recycled without destruction: re-init everything.
    s->fd_.store(options.fd, std::memory_order_relaxed);
    s->remote_side_ = options.remote_side;
    s->local_side_ = EndPoint();
    s->on_edge_triggered_events_ = options.on_edge_triggered_events;
    s->user_ = options.user;
    s->transport_ = options.transport;
    s->owns_transport_ = options.owns_transport;
    s->forced_tier_ = options.forced_transport_tier;
    s->write_head_.store(nullptr, std::memory_order_relaxed);
    s->write_pending_.store(0, std::memory_order_relaxed);
    s->unwritten_bytes_.store(0, std::memory_order_relaxed);
    s->inflight_batch_.clear();
    s->inflight_index_ = 0;
    s->writer_consumed_ = 0;
    s->nevent_.store(0, std::memory_order_relaxed);
    s->error_code_.store(0, std::memory_order_relaxed);
    s->connecting_.store(false, std::memory_order_relaxed);
    s->read_buf.clear();
    s->preferred_protocol_index = -1;
    s->pending_frame_bytes = 0;
    s->consumed_us = 0;
    *g_epollout_timeouts << 0;  // on /vars from the first scrape
    *g_epollout_timeouts_found_work << 0;
    s->health_check_interval_ms_ = options.health_check_interval_ms;
    s->tls_ = options.tls;
    s->tls_alpn_ = options.tls_alpn;
    s->tls_sni_ = options.tls_sni;
    s->hc_stop_.store(false, std::memory_order_relaxed);
    s->draining_.store(false, std::memory_order_relaxed);
    s->circuit_breaker_.ResetAll();
    // Install before any failure path below: AddConsumer failure recycles
    // the socket, which must still deliver the notification.
    s->on_recycle_ = options.on_recycle;
    s->recycle_arg_ = options.recycle_arg;
    s->conn_data_ = nullptr;
    s->conn_data_deleter_ = nullptr;
    s->bytes_read_.store(0, std::memory_order_relaxed);
    s->bytes_written_.store(0, std::memory_order_relaxed);
    s->descriptor_bytes_read_.store(0, std::memory_order_relaxed);
    s->peer_pool_id_.store(0, std::memory_order_relaxed);
    s->nwrite_batches_.store(0, std::memory_order_relaxed);
    s->max_write_batch_.store(0, std::memory_order_relaxed);
    s->queued_highwater_.store(0, std::memory_order_relaxed);
    s->novercrowded_.store(0, std::memory_order_relaxed);
    s->rate_scrape_us_.store(0, std::memory_order_relaxed);
    s->rate_scrape_in_.store(0, std::memory_order_relaxed);
    s->rate_scrape_out_.store(0, std::memory_order_relaxed);
    s->created_us_ = monotonic_time_us();
    s->last_active_us_.store(s->created_us_, std::memory_order_relaxed);
    if (s->epollout_butex_ == nullptr) s->epollout_butex_ = butex_create();
    if (s->connect_butex_ == nullptr) s->connect_butex_ = butex_create();
    if (s->auth_butex_ == nullptr) s->auth_butex_ = butex_create();
    s->auth_state_.store(0, std::memory_order_relaxed);
    s->auth_user_.clear();

    if (options.fd >= 0) {
        make_non_blocking(options.fd);
        int one = 1;
        setsockopt(options.fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
        ApplySocketBufferSizes(options.fd);
        if (EventDispatcher::GetGlobalDispatcher(options.fd)
                .AddConsumer(*id, options.fd) != 0) {
            PLOG(ERROR) << "AddConsumer failed for fd=" << options.fd;
            Socket* addr = Address(*id);
            if (addr) {
                addr->SetFailed();
                addr->Dereference();
            }
            return -1;
        }
    }
    return 0;
}

namespace {
std::atomic<Socket::FailureObserver> g_failure_observer{nullptr};
std::atomic<Socket::ReviveObserver> g_revive_observer{nullptr};
}  // namespace

void Socket::set_failure_observer(FailureObserver ob) {
    g_failure_observer.store(ob, std::memory_order_release);
}

void Socket::set_revive_observer(ReviveObserver ob) {
    g_revive_observer.store(ob, std::memory_order_release);
}

void Socket::OnFailed() {
    // Upper-layer notification first: in-flight server calls on this
    // connection should learn of the death before the health-check
    // machinery starts resurrecting it.
    FailureObserver ob = g_failure_observer.load(std::memory_order_acquire);
    if (ob != nullptr) ob(id());
    // Wake anything parked on this socket so it observes the failure.
    butex_word(epollout_butex_)->fetch_add(1, std::memory_order_release);
    butex_wake_all(epollout_butex_);
    butex_word(connect_butex_)->fetch_add(1, std::memory_order_release);
    butex_wake_all(connect_butex_);
    butex_word(auth_butex_)->fetch_add(1, std::memory_order_release);
    butex_wake_all(auth_butex_);
    // Health check: keep the slot alive with our own ref and probe until
    // the remote answers, then Revive the SAME id (reference
    // src/brpc/details/health_check.cpp:140 HealthCheckTask).
    if (health_check_interval_ms_ > 0 &&
        !hc_stop_.load(std::memory_order_acquire)) {
        AddRef();  // released by HealthCheckLoop
        fiber_t tid;
        if (fiber_start_background(&tid, nullptr, HealthCheckThunk, this) !=
            0) {
            Dereference();
        }
    }
}

void* Socket::HealthCheckThunk(void* arg) {
    ((Socket*)arg)->HealthCheckLoop();
    return nullptr;
}

// Probe TCP connect with a bounded wait; returns 0 when the remote accepts.
static int ProbeConnect(const EndPoint& remote, int timeout_ms) {
    const int fd = ::socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (fd < 0) return -1;
    sockaddr_in addr;
    endpoint2sockaddr(remote, &addr);
    int rc = ::connect(fd, (sockaddr*)&addr, sizeof(addr));
    if (rc != 0 && errno == EINPROGRESS) {
        pollfd pfd{fd, POLLOUT, 0};
        rc = ::poll(&pfd, 1, timeout_ms) == 1 ? 0 : -1;
        if (rc == 0) {
            int err = 0;
            socklen_t len = sizeof(err);
            getsockopt(fd, SOL_SOCKET, SO_ERROR, &err, &len);
            rc = err == 0 ? 0 : -1;
        }
    }
    ::close(fd);
    return rc;
}

// GET `path` and require a 200 within timeout_ms (one short-lived
// connection; the socket being revived is not touched).
static bool ProbeHttpHealth(const EndPoint& remote, const std::string& path,
                            int timeout_ms) {
    const int fd = ::socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (fd < 0) return false;
    sockaddr_in addr;
    endpoint2sockaddr(remote, &addr);
    int rc = ::connect(fd, (sockaddr*)&addr, sizeof(addr));
    if (rc != 0 && errno != EINPROGRESS) {
        close(fd);
        return false;
    }
    pollfd pfd{fd, POLLOUT, 0};
    if (rc != 0 && ::poll(&pfd, 1, timeout_ms) != 1) {
        close(fd);
        return false;
    }
    const std::string req =
        "GET " + path + " HTTP/1.1\r\nHost: hc\r\nConnection: close\r\n\r\n";
    if (send(fd, req.data(), req.size(), MSG_NOSIGNAL) !=
        (ssize_t)req.size()) {
        close(fd);
        return false;
    }
    char buf[256];
    size_t got = 0;
    const int64_t deadline = monotonic_time_us() + timeout_ms * 1000;
    // Read until the status line is complete (first CRLF) — byte offsets
    // must not be assumed: "HTTP/1.0 200" and reason-phrase-less replies
    // are legal and gate revival just the same.
    while (got < sizeof(buf) - 1 && monotonic_time_us() < deadline &&
           memchr(buf, '\n', got) == nullptr) {
        pollfd rp{fd, POLLIN, 0};
        if (::poll(&rp, 1, 50) != 1) continue;
        const ssize_t r = recv(fd, buf + got, sizeof(buf) - 1 - got, 0);
        if (r <= 0) break;
        got += (size_t)r;
    }
    close(fd);
    buf[got] = '\0';
    int status = 0;
    if (sscanf(buf, "HTTP/%*d.%*d %d", &status) != 1) return false;
    return status >= 200 && status < 300;
}

void Socket::HealthCheckLoop() {
    const int64_t interval_us = (int64_t)health_check_interval_ms_ * 1000;
    // Breaker-tripped sockets stay isolated for a duration that doubles
    // per repeated trip; a TCP-alive-but-RPC-failing server would
    // otherwise flap isolate->revive every interval, eating ~a window of
    // failed user calls per cycle.
    const int64_t iso_us =
        (int64_t)circuit_breaker_.isolation_duration_ms() * 1000;
    bool first = true;
    while (!hc_stop_.load(std::memory_order_acquire)) {
        fiber_usleep(first && iso_us > interval_us ? iso_us : interval_us);
        first = false;
        if (hc_stop_.load(std::memory_order_acquire)) break;
        // Only probe/revive once every other ref is gone: then no KeepWrite
        // or event fiber can race the connection-state reset below.
        if (nref() > 1) continue;
        // App-level probe (reference health_check.cpp:51-107) subsumes
        // the TCP connect probe — a process that accepts TCP but cannot
        // answer stays isolated; without a configured path, the connect
        // probe alone gates revival.
        const std::string hc_path = FLAGS_health_check_path.get();
        if (hc_path.empty()) {
            if (ProbeConnect(remote_side_, 200) != 0) continue;
        } else if (!ProbeHttpHealth(remote_side_, hc_path, 500)) {
            continue;
        }
        if (ReviveAfterHealthCheck() == 0) {
            // StopHealthCheck may have raced the probe window: a revived
            // socket nobody tracks anymore would leak alive forever. Undo.
            if (hc_stop_.load(std::memory_order_acquire)) SetFailed();
            break;
        }
    }
    Dereference();
}

int Socket::ReviveAfterHealthCheck() {
    // Drop every remnant of the dead connection. We are the only ref.
    CloseFdAndDropQueued();
    write_pending_.store(0, std::memory_order_relaxed);
    unwritten_bytes_.store(0, std::memory_order_relaxed);
    nevent_.store(0, std::memory_order_relaxed);
    read_buf.clear();
    preferred_protocol_index = -1;
    pending_frame_bytes = 0;
    consumed_us = 0;
    error_code_.store(0, std::memory_order_relaxed);
    connecting_.store(false, std::memory_order_relaxed);
    local_side_ = EndPoint();
    circuit_breaker_.Reset();  // fresh windows for the revived server
    auth_state_.store(0, std::memory_order_relaxed);  // re-authenticate
    auth_user_.clear();
    // The drain announcement belonged to the previous (now restarted)
    // process: the revived server serves anew, so LBs must pick it again.
    draining_.store(false, std::memory_order_relaxed);
    const int rc = Revive();
    if (rc == 0) {
        *g_hc_revives << 1;
        LOG(INFO) << "Revived socket id=" << id()
                  << " remote=" << endpoint2str(remote_side_);
        // After the slot is LIVE: an ejected backend must re-enter via
        // the outlier probe ramp, not at full weight.
        ReviveObserver ob = g_revive_observer.load(std::memory_order_acquire);
        if (ob != nullptr) ob(id());
    }
    return rc;
}

namespace {
void* id_error_fiber(void* arg) {
    id_error((uint64_t)(uintptr_t)arg, TERR_FAILED_SOCKET);
    return nullptr;
}
}  // namespace

// Dropped (never-written) requests error-notify their RPCs — from a fresh
// fiber, never inline: OnRecycle can run under arbitrary locks (e.g.
// SocketMap::mu_ via Dereference) and the error handler may retry into
// those same locks.
void Socket::DropWriteRequest(WriteRequest* req) {
    if (req->notify_id != 0) {
        fiber_t tid;
        if (fiber_start_background(&tid, nullptr, id_error_fiber,
                                   (void*)(uintptr_t)req->notify_id) != 0) {
            id_error(req->notify_id, TERR_FAILED_SOCKET);
        }
    }
    delete req;
}

void Socket::OnRecycle() {
    CloseFdAndDropQueued();
    read_buf.clear();
    if (conn_data_ != nullptr) {
        if (conn_data_deleter_ != nullptr) conn_data_deleter_(conn_data_);
        conn_data_ = nullptr;
        conn_data_deleter_ = nullptr;
    }
    if (transport_ != nullptr) {
        if (owns_transport_) transport_->Release();
        transport_ = nullptr;
    }
    // Last: the recycle notification (quiesce signal for Acceptor/Server
    // teardown). After this fires the owner may free itself, so nothing
    // below may touch user_/recycle_arg_ again.
    if (on_recycle_ != nullptr) {
        auto cb = on_recycle_;
        void* arg = recycle_arg_;
        on_recycle_ = nullptr;
        recycle_arg_ = nullptr;
        cb(arg, id());
    }
}

// Shared teardown of a dead connection: close + deregister the fd and drop
// every queued write request (error-notifying their CallIds). Callers must
// be the sole toucher of write state (recycle: nref==0; revive: sole-ref
// health-check fiber).
void Socket::CloseFdAndDropQueued() {
    const int fd = fd_.exchange(-1, std::memory_order_acq_rel);
    if (fd >= 0) {
        EventDispatcher::GetGlobalDispatcher(fd).RemoveConsumer(fd);
        // A transport's doorbell fd is owned by the transport (its link
        // may outlive this socket); only plain TCP fds are ours to close.
        if (transport_ == nullptr) close(fd);
    }
    if (transport_ != nullptr) transport_->Close();
    // Pipelined calls whose replies will never arrive (same fiber-spawn
    // discipline as DropWriteRequest: the id's error handler runs user
    // completion code).
    for (const PipelinedInfo& pi : ResetPipelinedInfo()) {
        if (pi.id_wait == 0) continue;
        fiber_t tid;
        if (fiber_start_background(&tid, nullptr, id_error_fiber,
                                   (void*)(uintptr_t)pi.id_wait) != 0) {
            id_error(pi.id_wait, TERR_FAILED_SOCKET);
        }
    }
    for (size_t i = inflight_index_; i < inflight_batch_.size(); ++i) {
        DropWriteRequest(inflight_batch_[i]);
    }
    inflight_batch_.clear();
    inflight_index_ = 0;
    writer_consumed_ = 0;
    WriteRequest* head = write_head_.exchange(nullptr, std::memory_order_acq_rel);
    while (head != nullptr) {
        WriteRequest* next = head->next.load(std::memory_order_acquire);
        while (next == WriteRequest::unlinked()) {
            next = head->next.load(std::memory_order_acquire);
        }
        DropWriteRequest(head);
        head = next;
    }
}

int Socket::SetFailedWithError(int error_code) {
    error_code_.store(error_code, std::memory_order_release);
    return SetFailed();
}

// ---------------- write path ----------------

int Socket::Write(IOBuf* data, uint64_t notify_id, int64_t enqueued_us,
                  int fail_after) {
    if (Failed()) {
        errno = TERR_FAILED_SOCKET;
        return -1;
    }
    const int64_t sz = (int64_t)data->size();
    if (unwritten_bytes_.load(std::memory_order_relaxed) + sz >
        FLAGS_socket_max_unwritten_bytes.get()) {
        novercrowded_.fetch_add(1, std::memory_order_relaxed);
        *g_eovercrowded << 1;
        errno = TERR_OVERCROWDED;
        return -1;
    }
    WriteRequest* req = new WriteRequest;
    req->notify_id = notify_id;
    req->enqueued_us = enqueued_us;
    req->fail_after = fail_after;
    req->data.swap(*data);
    req->next.store(WriteRequest::unlinked(), std::memory_order_relaxed);
    const int64_t queued =
        unwritten_bytes_.fetch_add(sz, std::memory_order_relaxed) + sz;
    // Queued-write high-water: how deep the backlog got before the
    // writer caught up (per-socket + the process-wide gauge).
    if (queued > queued_highwater_.load(std::memory_order_relaxed)) {
        queued_highwater_.store(queued, std::memory_order_relaxed);
        queued_write_highwater_cell()->update_max(queued);
    }
    // Count the request BEFORE publishing it. The writer subtracts what it
    // consumed and retires when that reaches zero; were a request visible
    // before it is counted, the writer could consume it first, drive the
    // count through zero while still holding the role, and the next Write
    // would elect a second writer beside it. Counted-first, the count never
    // runs below the requests still owed, and a writer that sees a count
    // without its request just grabs again until the push lands.
    const bool elected =
        write_pending_.fetch_add(1, std::memory_order_acq_rel) == 0;
    WriteRequest* old = write_head_.exchange(req, std::memory_order_acq_rel);
    req->next.store(old, std::memory_order_release);
    if (!elected) return 0;  // an active writer owns the queue
    // Elected the writer. Inside a coalescing round, hold the flush: later
    // responses of this round pile onto the queue and leave in ONE writev
    // when the scope flushes (chaos mode keeps the per-write KeepWrite
    // discipline — its seams may sleep and must own their fiber).
    if (!__builtin_expect(fault_injection_enabled(), 0) &&
        WriteCoalesceScope::TryDefer(this)) {
        return 0;
    }
    StartKeepWriteIfNeeded();
    return 0;
}

// ---------------- write coalescing (ISSUE 7) ----------------

// Deferred-then-flushed elections: nonzero under load is the proof the
// run-to-completion path is merging same-socket responses.
static LazyAdder g_coalesced_writes("rpc_socket_coalesced_writes");

int64_t SocketCoalescedWrites() {
    return (*g_coalesced_writes).get_value();
}

namespace {
thread_local WriteCoalesceScope* g_write_scope = nullptr;
}  // namespace

WriteCoalesceScope::WriteCoalesceScope() {
    // One-time: flush-and-detach on fiber park (the parked fiber may
    // resume on another pthread; see task_group.h park hooks).
    static const bool hook_registered = [] {
        register_park_hook(&WriteCoalesceScope::FlushCurrent);
        return true;
    }();
    (void)hook_registered;
    if (g_write_scope == nullptr) {
        g_write_scope = this;
        armed_ = true;
    }
}

WriteCoalesceScope::~WriteCoalesceScope() {
    if (!armed_) return;
    FlushDeferred();
    // sched_park may have detached us (flushing on the old thread); only
    // clear the slot we still own.
    if (g_write_scope == this) g_write_scope = nullptr;
}

void WriteCoalesceScope::FlushDeferred() {
    for (int i = 0; i < nsockets_; ++i) {
        Socket* s = sockets_[i];
        // The deferred election is still ours: flush (inline first, then
        // a KeepWrite fiber for leftovers) or drain if the socket died
        // mid-round — exactly KeepWriteThunk's failed-socket duty.
        if (s->Failed()) {
            s->DrainWriteQueue();
        } else {
            s->StartKeepWriteIfNeeded();
        }
        s->Dereference();
    }
    nsockets_ = 0;
}

bool WriteCoalesceScope::TryDefer(Socket* s) {
    WriteCoalesceScope* scope = g_write_scope;
    if (scope == nullptr || scope->nsockets_ >= kMaxSockets) return false;
    // Only the ELECTED writer reaches here, and it stays elected until
    // the flush — the same socket can never be deferred twice in one
    // round, so no duplicate scan is needed.
    s->AddRef();
    scope->sockets_[scope->nsockets_++] = s;
    *g_coalesced_writes << 1;
    return true;
}

void WriteCoalesceScope::FlushCurrent() {
    WriteCoalesceScope* scope = g_write_scope;
    if (scope == nullptr) return;
    scope->FlushDeferred();
    scope->armed_ = false;
    g_write_scope = nullptr;
}

void Socket::StartKeepWriteIfNeeded() {
    // Try one inline non-blocking flush first (the common small-write case:
    // everything fits in the socket buffer, no fiber needed — reference
    // socket.cpp:1615 "write once in the calling thread").
    if (fd() >= 0) {
        if (FlushOnce(false)) return;  // fully drained + retired
    }
    // Leftovers (or not yet connected): hand off to a KeepWrite fiber.
    AddRef();  // ownership ref for the fiber; released there
    fiber_t tid;
    if (fiber_start_background(&tid, nullptr, &Socket::KeepWriteThunk,
                               (void*)(uintptr_t)id()) != 0) {
        Dereference();
        SetFailedWithError(TERR_INTERNAL);
    }
}

void* Socket::KeepWriteThunk(void* arg) {
    const SocketId id = (SocketId)(uintptr_t)arg;
    Socket* s = Address(id);
    if (s == nullptr) {
        // Socket failed before the fiber ran. We still own the writer role
        // (and the AddRef from StartKeepWriteIfNeeded pins the object):
        // drop the queued requests NOW — recycle-time cleanup is deferred
        // indefinitely on health-checked sockets — then balance the ref.
        Socket* raw = address_resource<Socket>(VRefSlot(id));
        if (raw != nullptr) {
            raw->DrainWriteQueue();
            raw->Dereference();
        }
        return nullptr;
    }
    SocketUniquePtr owned(s);
    s->Dereference();  // balance StartKeepWriteIfNeeded's AddRef
    s->KeepWrite();
    return nullptr;
}

void Socket::KeepWrite() {
    if (fd() < 0) {
        if (ConnectIfNot() != 0) {
            SetFailedWithError(errno ? errno : TERR_FAILED_SOCKET);
            DrainWriteQueue();
            return;
        }
    }
    while (true) {
        if (Failed()) {
            DrainWriteQueue();
            return;
        }
        if (FlushOnce(true)) return;  // retired (or failed + drained)
    }
}

void Socket::DrainWriteQueue() {
    int64_t& consumed = writer_consumed_;
    while (true) {
        if (inflight_index_ >= inflight_batch_.size()) {
            inflight_batch_.clear();
            inflight_index_ = 0;
            WriteRequest* grabbed =
                write_head_.exchange(nullptr, std::memory_order_acq_rel);
            for (WriteRequest* cur = grabbed; cur != nullptr;) {
                WriteRequest* next = cur->next.load(std::memory_order_acquire);
                while (next == WriteRequest::unlinked()) {
                    next = cur->next.load(std::memory_order_acquire);
                }
                inflight_batch_.push_back(cur);
                cur = next;
            }
        }
        if (inflight_index_ >= inflight_batch_.size()) {
            // Zero the member BEFORE the fetch_sub (see FlushOnce).
            const int64_t done = consumed;
            consumed = 0;
            const int64_t prev =
                write_pending_.fetch_sub(done, std::memory_order_acq_rel);
            if (prev == done) return;
            continue;  // racing Write slipped in: grab again
        }
        while (inflight_index_ < inflight_batch_.size()) {
            WriteRequest* req = inflight_batch_[inflight_index_];
            unwritten_bytes_.fetch_sub((int64_t)req->data.size(),
                                       std::memory_order_relaxed);
            DropWriteRequest(req);
            ++inflight_index_;
            ++consumed;
        }
    }
}

// The single-writer drain loop. Grabs LIFO segments from write_head_,
// reverses to FIFO, writevs across requests (the KeepWrite batching of
// reference socket.cpp:1920 DoWrite). Returns true when the writer retired
// (queue balanced) or the socket failed; false when it should continue
// (only with allow_block=false on EAGAIN).
bool Socket::FlushOnce(bool allow_block) {
    // Chaos mode routes EVERY write through the KeepWrite fiber: the
    // inline flush runs on the caller's fiber, possibly under its locks
    // (h2 senders hold the session mutex across Socket::Write), where an
    // injected delay's fiber_usleep could park and unlock a std::mutex
    // from another thread (UB). In the KeepWrite fiber every seam —
    // including the TLS/shm transports' own — may sleep safely.
    if (__builtin_expect(fault_injection_enabled(), 0) && !allow_block) {
        return false;  // caller spawns KeepWrite
    }
    // Emulated-WAN shaping (ISSUE 14): a shaped dcn-tier socket routes
    // every flush through the KeepWrite fiber too — the shaping sleep
    // must never park the caller's fiber under its locks. One member
    // load for the (vast) non-dcn majority.
    const bool shaped_dcn =
        __builtin_expect(forced_tier_ >= 0, 0) && transport_ == nullptr &&
        DcnShapingEnabled() && forced_tier_ == TierDcn();
    if (shaped_dcn && !allow_block) {
        return false;  // caller spawns KeepWrite
    }
    int64_t& consumed = writer_consumed_;
    while (true) {
        // Refill the owned batch.
        if (inflight_index_ >= inflight_batch_.size()) {
            inflight_batch_.clear();
            inflight_index_ = 0;
            WriteRequest* grabbed =
                write_head_.exchange(nullptr, std::memory_order_acq_rel);
            // Reverse newest->oldest chain into oldest-first order.
            std::vector<WriteRequest*> tmp;
            for (WriteRequest* cur = grabbed; cur != nullptr;) {
                WriteRequest* next = cur->next.load(std::memory_order_acquire);
                while (next == WriteRequest::unlinked()) {
                    next = cur->next.load(std::memory_order_acquire);
                }
                tmp.push_back(cur);
                cur = next;
            }
            inflight_batch_.assign(tmp.rbegin(), tmp.rend());
        }
        if (inflight_index_ >= inflight_batch_.size()) {
            // Nothing visible: try to retire.
            // The fetch_sub that reaches zero RELEASES the writer role: a
            // Write on another thread may be elected the instant it lands.
            // So the shared counter is zeroed first and only a local is
            // subtracted; touching writer_consumed_ after the fetch_sub
            // would clobber the next writer's count (wedged queue) or hand
            // it a stale one (second writer elected, double free).
            const int64_t done = consumed;
            consumed = 0;
            const int64_t prev =
                write_pending_.fetch_sub(done, std::memory_order_acq_rel);
            if (prev == done) return true;
            continue;  // more requests were queued; grab again
        }
        // Gather up to 64 iovecs from the batch tail.
        IOBuf* pieces[64];
        size_t npieces = 0;
        for (size_t i = inflight_index_;
             i < inflight_batch_.size() && npieces < 64; ++i) {
            pieces[npieces++] = &inflight_batch_[i]->data;
        }
        // Chaos seam (tnet/fault_injection.h): one flag load when
        // disabled; when a fault fires it replaces or perturbs this
        // round's writev. Plain-fd sockets only — TLS and shm transports
        // consult the injection layer inside their own
        // CutFromIOBufList/Pump (stacking both seams would double-count
        // decisions and double the effective fault rate), mirroring the
        // transport()==nullptr gate on the read path.
        ssize_t nw = 0;
        bool fault_io = false;
        if (__builtin_expect(fault_injection_enabled(), 0) &&
            transport_ == nullptr) {
            size_t total = 0;
            for (size_t i = 0; i < npieces; ++i) total += pieces[i]->size();
            const FaultAction fa =
                FaultInjection::Decide(FaultOp::kWrite, remote_side_, total);
            switch (fa.kind) {
                case FaultAction::kReset:
                    SetFailedWithError(ECONNRESET);
                    DrainWriteQueue();
                    return true;
                case FaultAction::kDelay:
                    // Safe: chaos mode runs every flush on the
                    // KeepWrite fiber (see the gate at the top).
                    fiber_usleep(fa.delay_us);
                    break;
                case FaultAction::kDrop:
                    // Claim success, discard the bytes: the peer sees a
                    // truncated stream (parse error / stall) and this
                    // side's RPCs ride their timeouts.
                    for (size_t i = 0; i < npieces; ++i) {
                        pieces[i]->pop_front(pieces[i]->size());
                    }
                    nw = (ssize_t)total;
                    fault_io = true;
                    break;
                case FaultAction::kShort:
                case FaultAction::kCorrupt: {
                    // Write a bounded copied prefix (flipping one byte
                    // for kCorrupt — never mutate the shared IOBuf
                    // blocks in place) and let the normal partial-write
                    // machinery handle the remainder.
                    char tmp[2048];
                    IOBuf* first = pieces[0];
                    size_t n = std::min(first->size(), sizeof(tmp));
                    if (fa.kind == FaultAction::kShort && fa.max_bytes > 0) {
                        n = std::min(n, fa.max_bytes);
                    }
                    n = first->copy_to(tmp, n);
                    if (n == 0) break;
                    if (fa.kind == FaultAction::kCorrupt) {
                        tmp[fa.aux % n] ^= 0x20;
                    }
                    const ssize_t w = ::write(fd(), tmp, n);
                    if (w > 0) first->pop_front((size_t)w);
                    nw = w;
                    fault_io = true;
                    break;
                }
                default:
                    break;
            }
        }
        // Emulated-WAN shaping: park for the configured latency + byte
        // time before this round's bytes leave. Runs on the KeepWrite
        // fiber only (the shaped_dcn gate above). A partial write
        // re-shapes its remainder next round — the emulated pipe is a
        // floor, not an exact clock.
        if (shaped_dcn && !fault_io) {
            size_t total = 0;
            for (size_t i = 0; i < npieces; ++i) total += pieces[i]->size();
            const int64_t d = DcnShapeDelayUs(transport_tier(), total);
            if (d > 0) fiber_usleep(d);
        }
        // Data plane: ICI queue pair when plugged (the RdmaEndpoint
        // bypass — reference socket.cpp checks _rdma_state on the write
        // path), else the fd.
        // Stage clock: the stamp the link took as it posted, if it
        // takes one.
        int64_t posted_us = 0;
        if (!fault_io) {
            nw = transport_ != nullptr
                     ? transport_->CutFromIOBufList(pieces, npieces,
                                                    &posted_us)
                     : IOBuf::cut_multiple_into_file_descriptor(fd(), pieces,
                                                                npieces);
        }
        if (nw < 0) {
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                if (!allow_block) return false;  // caller spawns KeepWrite
                // Out of window credits (queue-pair tiers) or kernel
                // buffer (fd tier): the writer is about to park.
                transport_stats::AddCreditStall(transport_tier());
                const int wrc =
                    transport_ != nullptr
                        ? transport_->WaitWritable(monotonic_time_us() +
                                                   2 * 1000 * 1000)
                        : WaitEpollOut();
                if (wrc != 0) {
                    SetFailedWithError(TERR_FAILED_SOCKET);
                    DrainWriteQueue();
                    return true;
                }
                continue;
            }
            if (errno == EINTR) continue;
            SetFailedWithError(errno);
            DrainWriteQueue();
            return true;
        }
        unwritten_bytes_.fetch_sub(nw, std::memory_order_relaxed);
        add_bytes_written(nw);
        if (nw > 0) {
            // Per-tier byte attribution (the Transport seam, ISSUE 12).
            transport_stats::AddOut(transport_tier(), nw);
            transport_stats::AddOp(transport_tier());
            // Write-batch attribution: one writev round = one batch.
            nwrite_batches_.fetch_add(1, std::memory_order_relaxed);
            if (nw > max_write_batch_.load(std::memory_order_relaxed)) {
                max_write_batch_.store(nw, std::memory_order_relaxed);
            }
            *write_batch_recorder() << nw;
        }
        // Drop fully-written requests; a stamped one ends its
        // tnet.write_queue here, on the link's post stamp, else on one
        // clock read for the whole round.
        while (inflight_index_ < inflight_batch_.size() &&
               inflight_batch_[inflight_index_]->data.empty()) {
            const int64_t enqueued_us =
                inflight_batch_[inflight_index_]->enqueued_us;
            if (enqueued_us != 0) {
                if (posted_us == 0) posted_us = stage::now_us();
                stage::Add(stage::kWriteQueue, posted_us - enqueued_us);
            }
            const int fail_after =
                inflight_batch_[inflight_index_]->fail_after;
            delete inflight_batch_[inflight_index_];
            ++inflight_index_;
            ++consumed;
            if (__builtin_expect(fail_after != 0, 0)) {
                SetFailedWithError(fail_after);
                DrainWriteQueue();
                return true;
            }
        }
    }
}

int Socket::WaitAuthenticated(int64_t abstime_us) {
    std::atomic<int>* word = butex_word(auth_butex_);
    while (true) {
        const int st = auth_state_.load(std::memory_order_acquire);
        if (st == 2 || st == 0) break;  // done, or aborted (re-fight)
        if (Failed()) return -1;
        const int expected = word->load(std::memory_order_acquire);
        const int st2 = auth_state_.load(std::memory_order_acquire);
        if (st2 == 2 || st2 == 0) break;
        if (abstime_us > 0 && monotonic_time_us() >= abstime_us) return -1;
        const int64_t slice =
            abstime_us > 0
                ? std::min<int64_t>(abstime_us,
                                    monotonic_time_us() + 200 * 1000)
                : monotonic_time_us() + 200 * 1000;
        butex_wait(auth_butex_, expected, &slice);
    }
    return Failed() ? -1 : 0;
}

int Socket::WaitEpollOut() {
    const int the_fd = fd();
    if (the_fd < 0) return -1;
    std::atomic<int>* word = butex_word(epollout_butex_);
    const int expected = word->load(std::memory_order_acquire);
    EventDispatcher& d = EventDispatcher::GetGlobalDispatcher(the_fd);
    if (d.RegisterEpollOut(id(), the_fd, true) != 0) return -1;
    const int64_t abstime = monotonic_time_us() + 2 * 1000 * 1000;
    if (butex_wait(epollout_butex_, expected, &abstime) == ETIMEDOUT) {
        // Safety net: the 2 s re-check, not an EPOLLOUT event, ended the
        // wait; an fd writable by now means the event was lost.
        *g_epollout_timeouts << 1;
        pollfd pfd{the_fd, POLLOUT, 0};
        if (poll(&pfd, 1, 0) > 0 && (pfd.revents & POLLOUT) != 0) {
            *g_epollout_timeouts_found_work << 1;
        }
    }
    d.UnregisterEpollOut(id(), the_fd, true);
    return Failed() ? -1 : 0;
}

// ---------------- connect ----------------

int Socket::ConnectIfNot() {
    if (fd() >= 0) return 0;
    bool expected = false;
    if (!connecting_.compare_exchange_strong(expected, true)) {
        // Another fiber connects; wait for it.
        std::atomic<int>* word = butex_word(connect_butex_);
        while (fd() < 0 && !Failed()) {
            const int v = word->load(std::memory_order_acquire);
            if (fd() >= 0 || Failed()) break;
            const int64_t abst = monotonic_time_us() + 100 * 1000;
            butex_wait(connect_butex_, v, &abst);
        }
        return (fd() >= 0 && !Failed()) ? 0 : -1;
    }
    // Chaos: connect-time refusal — the client-side mirror of the
    // acceptor's refuse (exercises retry + LB re-selection).
    if (__builtin_expect(fault_injection_enabled(), 0) &&
        FaultInjection::Decide(FaultOp::kConnect, remote_side_, 0).kind ==
            FaultAction::kRefuse) {
        connecting_.store(false, std::memory_order_release);
        errno = ECONNREFUSED;
        return -1;
    }
    const int sock = ::socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (sock < 0) {
        connecting_.store(false, std::memory_order_release);
        return -1;
    }
    int one = 1;
    setsockopt(sock, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
    ApplySocketBufferSizes(sock);
    sockaddr_in addr;
    endpoint2sockaddr(remote_side_, &addr);
    int rc = ::connect(sock, (sockaddr*)&addr, sizeof(addr));
    if (rc != 0 && errno != EINPROGRESS) {
        close(sock);
        connecting_.store(false, std::memory_order_release);
        return -1;
    }
    EventDispatcher& d = EventDispatcher::GetGlobalDispatcher(sock);
    std::atomic<int>* word = butex_word(connect_butex_);
    int seq = word->load(std::memory_order_acquire);
    if (d.AddConsumerWithEpollOut(id(), sock) != 0) {
        close(sock);
        connecting_.store(false, std::memory_order_release);
        return -1;
    }
    if (rc != 0) {
        // Await writability (= connect completion), 3s cap.
        const int64_t deadline = monotonic_time_us() + 3 * 1000 * 1000;
        while (!Failed()) {
            int err = 0;
            socklen_t len = sizeof(err);
            getsockopt(sock, SOL_SOCKET, SO_ERROR, &err, &len);
            if (err != 0) {
                errno = err;
                break;
            }
            // Poll connection state cheaply: getpeername succeeds once
            // connected.
            sockaddr_in peer;
            socklen_t plen = sizeof(peer);
            if (getpeername(sock, (sockaddr*)&peer, &plen) == 0) {
                rc = 0;
                break;
            }
            if (monotonic_time_us() >= deadline) {
                errno = ETIMEDOUT;
                break;
            }
            const int64_t abst = monotonic_time_us() + 50 * 1000;
            butex_wait(connect_butex_, seq, &abst);
            seq = word->load(std::memory_order_acquire);
        }
        if (rc != 0 || Failed()) {
            d.RemoveConsumer(sock);
            close(sock);
            connecting_.store(false, std::memory_order_release);
            word->fetch_add(1, std::memory_order_release);
            butex_wake_all(connect_butex_);
            return -1;
        }
    }
    // Connected: record sides, drop EPOLLOUT interest.
    sockaddr_in local;
    socklen_t llen = sizeof(local);
    if (getsockname(sock, (sockaddr*)&local, &llen) == 0) {
        local_side_ = sockaddr2endpoint(local);
    }
    d.UnregisterEpollOut(id(), sock, true);
    if (tls_) {
        // Wrap the freshly connected fd BEFORE fd_ becomes visible, so
        // every write/read path sees the transport together with the fd.
        TransportEndpoint* t =
            NewTlsClientTransport(sock, tls_alpn_, tls_sni_);
        if (t == nullptr) {
            d.RemoveConsumer(sock);
            close(sock);
            connecting_.store(false, std::memory_order_release);
            word->fetch_add(1, std::memory_order_release);
            butex_wake_all(connect_butex_);
            return -1;
        }
        transport_ = t;
        owns_transport_ = true;
    }
    fd_.store(sock, std::memory_order_release);
    connecting_.store(false, std::memory_order_release);
    word->fetch_add(1, std::memory_order_release);
    butex_wake_all(connect_butex_);
    return 0;
}

// ---------------- read events ----------------

void Socket::OnInputEventById(SocketId id) {
    Socket* s = Address(id);
    if (s == nullptr) return;
    SocketUniquePtr ptr(s);
    if (s->on_edge_triggered_events_ == nullptr) return;
    if (s->nevent_.fetch_add(1, std::memory_order_acq_rel) == 0) {
        // First event of a burst: elect one processing fiber.
        s->AddRef();
        fiber_t tid;
        if (fiber_start_background(&tid, nullptr, &Socket::ProcessEventThunk,
                                   (void*)(uintptr_t)id) != 0) {
            s->Dereference();
            s->nevent_.store(0, std::memory_order_release);
        }
    }
}

void* Socket::ProcessEventThunk(void* arg) {
    const SocketId id = (SocketId)(uintptr_t)arg;
    Socket* s = Address(id);
    if (s == nullptr) {
        // Balance the AddRef: the socket was failed but memory persists.
        // (Address failed => versioned ref says stale; the extra ref we
        // took in OnInputEventById still pins the object.)
        s = address_resource<Socket>(VRefSlot(id));
        if (s != nullptr) s->Dereference();
        return nullptr;
    }
    SocketUniquePtr ptr(s);
    s->Dereference();  // balance OnInputEventById's AddRef
    while (true) {
        const int n = s->nevent_.load(std::memory_order_acquire);
        // fd() < 0 means an async connect is still in flight: EPOLLERR/HUP
        // on the connecting fd routes here too, but the read callback must
        // not run against fd -1 (the connect loop surfaces the error).
        if (!s->Failed() && s->on_edge_triggered_events_ != nullptr &&
            s->fd() >= 0) {
            s->on_edge_triggered_events_(s);
        }
        if (s->nevent_.fetch_sub(n, std::memory_order_acq_rel) == n) {
            break;
        }
    }
    return nullptr;
}

void Socket::OnOutputEventById(SocketId id) {
    Socket* s = Address(id);
    if (s == nullptr) return;
    SocketUniquePtr ptr(s);
    // Wake connecters and blocked writers.
    butex_word(s->connect_butex_)->fetch_add(1, std::memory_order_release);
    butex_wake_all(s->connect_butex_);
    butex_word(s->epollout_butex_)->fetch_add(1, std::memory_order_release);
    butex_wake_all(s->epollout_butex_);
}

}  // namespace tpurpc
