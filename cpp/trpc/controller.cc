#include "trpc/auth.h"
#include "trpc/controller.h"

#include <google/protobuf/descriptor.h>

#include <algorithm>
#include <cstdarg>
#include <cstdint>

#include "tvar/reducer.h"

#include "rpc_meta.pb.h"
#include "tbase/errno.h"
#include "thttp/http2_client.h"
#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "tnet/socket_map.h"
#include "trpc/channel.h"
#include "trpc/lb_with_naming.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tnet/fault_injection.h"
#include "trpc/pb_compat.h"
#include "trpc/retry_policy.h"
#include "trpc/policy_tpu_std.h"
#include "tbase/crc32c.h"
#include "trpc/compress.h"
#include "trpc/span.h"
#include "trpc/stream.h"
#include "tvar/stage_recorder.h"

DEFINE_bool(rpc_checksum, false,
            "crc32c-protect tpu_std frame bodies (verified when present)");
DECLARE_bool(chaos_enabled);
DECLARE_string(rpc_zone);

#include "trpc/server_call.h"

namespace tpurpc {

// Client-side re-issue observability: the chaos soak bounds total
// re-issues (retries + backups) against the configured retry budget.
static LazyAdder g_client_retries("rpc_client_retries");
static LazyAdder g_client_backups("rpc_client_backup_requests");
static LazyAdder g_budget_exhausted("rpc_retry_budget_exhausted");
// Drain steering: new calls routed around a draining server (LB skip),
// and re-issues of calls a draining server provably never processed.
// Both are budget-free — the rolling-restart soak asserts zero retry
// tokens spent across a full mesh restart.
static LazyAdder g_drain_reroutes("rpc_client_drain_reroutes");

// Shared with the combo-channel retry loops (controller.h client_stats):
// one process-wide adder per name, whoever drives the re-issue.
namespace client_stats {
void CountRetry() { *g_client_retries << 1; }
void CountBudgetExhausted() { *g_budget_exhausted << 1; }
}  // namespace client_stats
// One-sided descriptor sends (ISSUE 9): calls whose attachment crossed
// the wire as a (pool_id, offset, len, crc) reference — and the logical
// bytes that never entered the frame/copy path because of it.
static LazyAdder g_pool_desc_sends("rpc_pool_descriptor_sends");
static LazyAdder g_pool_desc_bytes("rpc_pool_descriptor_send_bytes");
// Ineligible set_request_pool_attachment calls folded back to the
// inline path (multi-block or non-shared memory).
static LazyAdder g_pool_desc_fallbacks("rpc_pool_descriptor_fallbacks");
// Leases released by EndRPC that were ALREADY reclaimed underneath the
// call (expiry reaper / peer death): the stale-descriptor signature.
static LazyAdder g_pool_lease_gone("rpc_pool_lease_already_reclaimed");
// Tries whose pinned request attachment went INLINE because the try's
// transport tier cannot carry a descriptor (plain TCP pick by the LB):
// same payload on the wire, copied — eligibility decided at the
// Transport seam instead of failing on the server (ISSUE 12).
static LazyAdder g_pool_desc_wire_fallbacks(
    "rpc_pool_descriptor_wire_fallbacks");

void Controller::set_request_pool_attachment(IOBuf&& buf) {
    // A second call replaces the first attachment: release the prior
    // lease or its pin would be orphaned for good (overwriting the id
    // alone leaks the slab slot).
    ReleasePoolLease();
    // Eligibility is decided HERE, once, not per retry: the bytes must
    // be one contiguous block ref inside the shared registered pool so
    // a single (offset, len) names them all. Anything else falls back
    // to the inline attachment — same payload on the wire, just copied.
    uint64_t off = 0;
    size_t flen = 0;
    const char* data =
        buf.backing_block_num() == 1 ? buf.backing_block_data(0, &flen)
                                     : nullptr;
    if (data != nullptr && flen == buf.size() &&
        IciBlockPool::OffsetOf(data, &off) &&
        IciBlockPool::pool_id() != 0) {
        // Stash the resolved descriptor (crc computed ONCE — retries
        // re-send the same reference without re-reading the bytes) and
        // hand the pin to the lease registry: from here the block's
        // lifetime is crash-safe (exactly-once release, expiry reaper,
        // peer-death reclamation) instead of riding this controller.
        pool_attachment_.data = data;
        pool_attachment_.length = flen;
        pool_attachment_.pool_id = IciBlockPool::pool_id();
        pool_attachment_.offset = off;
        pool_attachment_.crc32c = crc32c_extend(0, data, flen);
        pool_attachment_.pool_epoch = IciBlockPool::pool_epoch();
        pool_lease_id_ = block_lease::Pin(std::move(buf));
        return;
    }
    *g_pool_desc_fallbacks << 1;
    request_attachment_.append(std::move(buf));
}

// One-sided completion (ISSUE 10a): release the pinned block back to
// the owner's pool — the descriptor analog of the shm ring's released_-
// counter advance. Exactly-once across every termination path (EndRPC,
// Reset-for-reuse, destruction, retry/backup re-issues): the lease
// registry arbitrates, so a pin the reaper or peer-death path already
// reclaimed is a counted no-op here, never a double free. The chaos
// leak simulation (chaos_pool pool_leak) "forgets" this release so the
// soak can prove the reaper reclaims orphaned pins.
void Controller::ReleasePoolLease() {
    if (pool_lease_id_ == 0) return;
    const uint64_t id = pool_lease_id_;
    pool_lease_id_ = 0;
    if (__builtin_expect(fault_injection_enabled(), 0)) {
        const FaultAction fault = FaultInjection::Decide(
            FaultOp::kLeaseRelease, remote_side_, 0);
        if (fault.kind == FaultAction::kDrop) {
            return;  // leaked on purpose: the reaper must reclaim it
        }
    }
    if (!block_lease::Release(id)) {
        *g_pool_lease_gone << 1;
    }
}

// Response-direction twin of set_request_pool_attachment (ISSUE 12):
// the handler answers with a pool-block reference. Eligibility adds one
// check the request side decides at IssueRPC time instead — the CALL's
// connection must ride a descriptor-capable transport tier (the client
// either mapped our pool at handshake or is this process); on an
// ineligible shape or tier the bytes fall back to the inline response
// attachment, so handlers never need to know the transport.
void Controller::set_response_pool_attachment(IOBuf&& buf) {
    // Replacing a prior response attachment releases its pin first.
    if (rsp_pool_lease_id_ != 0) {
        block_lease::Release(rsp_pool_lease_id_);
        rsp_pool_lease_id_ = 0;
        rsp_pool_stash_ = PoolAttachment();
    }
    uint64_t off = 0;
    size_t flen = 0;
    const char* data =
        buf.backing_block_num() == 1 ? buf.backing_block_data(0, &flen)
                                     : nullptr;
    bool tier_ok = false;
    if (server_socket_ != INVALID_VREF_ID) {
        SocketUniquePtr s;
        if (Socket::AddressSocket(server_socket_, &s) == 0) {
            tier_ok = TransportDescriptorCapable(s.get());
        }
    }
    if (tier_ok && data != nullptr && flen == buf.size() &&
        IciBlockPool::OffsetOf(data, &off) &&
        IciBlockPool::pool_id() != 0) {
        rsp_pool_stash_.data = data;
        rsp_pool_stash_.length = flen;
        rsp_pool_stash_.pool_id = IciBlockPool::pool_id();
        rsp_pool_stash_.offset = off;
        rsp_pool_stash_.crc32c = crc32c_extend(0, data, flen);
        rsp_pool_stash_.pool_epoch = IciBlockPool::pool_epoch();
        rsp_pool_lease_id_ = block_lease::Pin(std::move(buf), "rsp");
        return;
    }
    rsp_desc::CountFallback();
    response_attachment_.append(std::move(buf));
}

void Controller::ReleaseResponsePoolState() {
    // Server role: a pin whose ownership the response closure never
    // took (failed call, handler ran on a non-tpu_std protocol whose
    // response path ignores descriptors) must not outlive the
    // controller. Exactly-once through the registry as always.
    if (rsp_pool_lease_id_ != 0) {
        block_lease::Release(rsp_pool_lease_id_);
        rsp_pool_lease_id_ = 0;
    }
    rsp_pool_stash_ = PoolAttachment();
    // Client role: releasing the view acks the server's pin. Best-
    // effort — a dead connection drops the ack and the server's reaper
    // reclaims instead.
    if (rsp_ack_sid_ != INVALID_VREF_ID && rsp_ack_cid_ != 0) {
        SendTpuStdDescAck(rsp_ack_sid_, rsp_ack_cid_,
                          rsp_pool_view_.ack_token);
    }
    rsp_pool_view_ = PoolAttachment();
    rsp_ack_sid_ = INVALID_VREF_ID;
    rsp_ack_cid_ = 0;
}

Controller::~Controller() {
    RunCancelClosure();  // contract: an unfired closure still runs once
    ReleasePoolLease();  // a pin must not outlive its controller
    ReleaseResponsePoolState();  // ack the peer's pin / drop our own
    delete excluded_;
    delete span_;  // non-null only if the RPC never reached EndRPC/submit
}

void Controller::Reset() {
    RunCancelClosure();  // reuse ends the previous RPC: fire if unfired
    error_code_ = 0;
    error_text_.clear();
    timeout_ms_ = -1;   // -1: use the channel default
    max_retry_ = -1;
    log_id_ = 0;
    canceled_.store(false, std::memory_order_relaxed);
    request_attachment_.clear();
    response_attachment_.clear();
    ReleasePoolLease();  // reuse ends the previous RPC's pin
    pool_attachment_ = PoolAttachment();
    ReleaseResponsePoolState();  // reuse acks/releases the rsp direction
    remote_side_ = EndPoint();
    local_side_ = EndPoint();
    latency_us_ = 0;
    channel_ = nullptr;
    method_ = nullptr;
    response_ = nullptr;
    done_ = nullptr;
    correlation_id_ = INVALID_CALL_ID;
    current_cid_ = INVALID_CALL_ID;
    unfinished_cid_ = INVALID_CALL_ID;
    backup_timer_ = INVALID_TIMER_ID;
    backup_request_ms_ = -1;
    request_buf_.clear();
    current_try_ = 0;
    start_us_ = 0;
    deadline_us_ = 0;
    timeout_timer_ = INVALID_TIMER_ID;
    single_server_id_ = INVALID_VREF_ID;
    current_server_id_ = INVALID_VREF_ID;
    try_start_us_ = 0;
    reply_parsed_us_ = 0;
    request_code_ = 0;
    has_request_code_ = false;
    request_compress_type_ = 0;
    response_compress_type_ = 0;
    tenant_.clear();
    priority_ = -1;
    session_.clear();
    suggested_backoff_ms_ = 0;
    unfinished_server_id_ = INVALID_VREF_ID;
    backup_issued_ = false;
    backup_won_ = false;
    current_fly_sid_ = INVALID_VREF_ID;
    unfinished_fly_sid_ = INVALID_VREF_ID;
    reusable_fly_sid_ = INVALID_VREF_ID;
    auth_fight_sid_ = INVALID_VREF_ID;
    delete excluded_;
    excluded_ = nullptr;
    request_stream_ = INVALID_VREF_ID;
    request_stream_window_ = 0;
    request_stream_bound_ = false;
    has_remote_stream_ = false;
    remote_stream_id_ = 0;
    remote_stream_window_ = 0;
    accepted_stream_ = INVALID_VREF_ID;
    accepted_stream_window_ = 0;
    push_open_id_ = 0;
    push_open_rx_window_ = 0;
    push_open_resume_from_ = 0;
    has_push_open_ = false;
    accepted_push_stream_ = 0;
    server_socket_ = INVALID_VREF_ID;
    server_ = nullptr;
    server_deadline_us_ = 0;
    server_call_id_ = INVALID_CALL_ID;
    {
        std::lock_guard<std::mutex> g(child_mu_);
        child_calls_.clear();
    }
    span_ = nullptr;
    sampled_trace_id_ = 0;
}

void Controller::SetFailed(const std::string& reason) {
    error_code_ = TERR_INTERNAL;
    error_text_ = reason;
}

void Controller::SetFailed(int error_code, const char* fmt, ...) {
    error_code_ = error_code;
    char buf[512];
    va_list ap;
    va_start(ap, fmt);
    vsnprintf(buf, sizeof(buf), fmt, ap);
    va_end(ap);
    error_text_ = buf;
}

void Controller::StartCancel() {
    canceled_.store(true, std::memory_order_release);
    if (correlation_id_ != INVALID_CALL_ID) {
        // HandleError(ECANCELED) sends the wire CANCEL for the in-flight
        // tries under the id lock and finishes the RPC.
        id_error(correlation_id_, ECANCELED);
    }
}

void Controller::NotifyOnCancel(google::protobuf::Closure* closure) {
    if (closure == nullptr) return;
    if (canceled_.load(std::memory_order_acquire)) {
        closure->Run();  // already canceled: notify immediately
        return;
    }
    google::protobuf::Closure* prev =
        on_cancel_.exchange(closure, std::memory_order_acq_rel);
    if (prev != nullptr) {
        prev->Run();  // replaced: the displaced closure still runs once
    }
    if (canceled_.load(std::memory_order_acquire)) {
        RunCancelClosure();  // lost a race with a concurrent cancel
    }
}

void Controller::RunCancelClosure() {
    google::protobuf::Closure* c =
        on_cancel_.exchange(nullptr, std::memory_order_acq_rel);
    if (c != nullptr) c->Run();
}

bool Controller::AddChildCall(CallId cid) {
    std::lock_guard<std::mutex> g(child_mu_);
    if (canceled_.load(std::memory_order_acquire)) return false;
    // Children are never individually deregistered (id_error on a
    // completed id is a free no-op), so a long-lived handler issuing
    // thousands of sequential calls would grow this without bound:
    // compact the dead ids once the list gets big. RANGE existence, not
    // strict: a child that retried (version bump) is still live and
    // still cancelable through its original id value.
    if (child_calls_.size() >= 256) {
        child_calls_.erase(
            std::remove_if(child_calls_.begin(), child_calls_.end(),
                           [](CallId c) { return !id_exists_range(c); }),
            child_calls_.end());
    }
    child_calls_.push_back(cid);
    return true;
}

// ---------------- server-side cancellation ----------------

int64_t Controller::remaining_server_budget_us() const {
    if (server_deadline_us_ <= 0) return INT64_MAX;
    return server_deadline_us_ - monotonic_time_us();
}

namespace {
// Deferred cascade delivery (plain CallId VALUES: stale-safe, never
// touches the possibly-already-freed parent controller).
void* CancelChildrenFiber(void* arg) {
    auto* children = (std::vector<CallId>*)arg;
    for (CallId c : *children) {
        id_error(c, ECANCELED);
    }
    delete children;
    return nullptr;
}
void CancelChildrenTimerCb(void* arg) { CancelChildrenFiber(arg); }
}  // namespace

void Controller::HandleServerCancel() {
    if (canceled_.exchange(true, std::memory_order_acq_rel)) {
        return;  // duplicate delivery (second CANCEL meta, RST + death)
    }
    server_call::CountCanceled();
    RunCancelClosure();
    // Cascade into the handler's downstream calls. canceled_ was set
    // BEFORE taking child_mu_, so a racing AddChildCall either landed in
    // the swapped list or observes canceled_ and self-cancels.
    //
    // Delivery happens OFF this fiber: we run under the server-call id
    // lock, and a child's inline completion can re-enter the SERVER
    // call's done closure (async proxy handlers), whose
    // DestroyServerCallId would then block on the very lock this fiber
    // holds — a self-deadlock. A fresh fiber (timer thread as backstop)
    // takes the child ids by value, so the parent may die freely.
    auto* children = new std::vector<CallId>;
    {
        std::lock_guard<std::mutex> g(child_mu_);
        children->swap(child_calls_);
    }
    if (children->empty()) {
        delete children;
        return;
    }
    fiber_t tid;
    if (fiber_start_background(&tid, nullptr, CancelChildrenFiber,
                               children) != 0) {
        TimerThread::singleton()->schedule(CancelChildrenTimerCb, children,
                                           monotonic_time_us());
    }
}

int Controller::HandleServerCancelThunk(CallId id, void* data, int) {
    ((Controller*)data)->HandleServerCancel();
    return id_unlock(id);  // the call stays live; done destroys the id
}

void Controller::DestroyServerCallId() {
    if (server_call_id_ == INVALID_CALL_ID) return;
    void* unused;
    // Serializes behind an in-flight cancel delivery (the thunk holds the
    // lock while touching this controller), then drops any still-queued
    // cancels — the response is already on its way out.
    if (id_lock(server_call_id_, &unused) == 0) {
        id_unlock_and_destroy(server_call_id_);
    }
    server_call_id_ = INVALID_CALL_ID;
}

void Controller::SendWireCancel() {
    if (channel_ == nullptr) return;
    const bool grpc = channel_->options().protocol == "grpc";
    const auto send_one = [&](CallId cid, SocketId fly_sid,
                              SocketId server_sid) {
        if (cid == INVALID_CALL_ID) return;
        SocketId sid = fly_sid;
        if (sid == INVALID_VREF_ID) sid = server_sid;
        if (sid == INVALID_VREF_ID) sid = single_server_id_;
        if (sid == INVALID_VREF_ID) return;
        if (grpc) {
            H2ClientCancel(sid, cid);
        } else {
            SendTpuStdCancel(sid, cid);
        }
    };
    send_one(current_cid_, current_fly_sid_, current_server_id_);
    // The unfinished (pre-backup) try lives on ITS OWN server: the
    // backup's FeedbackToLB cleared current_server_id_, so the saved
    // unfinished_server_id_ is the only address that still names it.
    send_one(unfinished_cid_, unfinished_fly_sid_, unfinished_server_id_);
}

// ---------------- client call machinery ----------------

int Controller::HandleErrorThunk(CallId id, void* data, int error) {
    return ((Controller*)data)->HandleError(id, error);
}

static bool is_retryable(int error) {
    // The default retry policy (reference src/brpc/retry_policy.cpp
    // DefaultRetryPolicy: EFAILEDSOCKET/EEOF/EHOSTDOWN/...): connection-
    // level failures retry, server-side/user errors and timeouts don't.
    switch (error) {
        case TERR_FAILED_SOCKET:
        case TERR_EOF:
        case TERR_OVERCROWDED:
        case ECONNREFUSED:
        case ECONNRESET:
        case EPIPE:
        case EHOSTDOWN:  // LB found only failed servers; retry re-selects
        case TERR_DRAINING:  // peer draining, call provably unprocessed
        // Priority-aware overload shed: the server never ran the
        // handler, so a re-issue (elsewhere, after the suggested
        // backoff) is safe — but it SPENDS retry budget, because under
        // overload re-issues amplify the very load being shed.
        case TERR_OVERLOAD:
        // Stale zero-copy reference (pool epoch fence): the server
        // refused to resolve a descriptor minted under an old pool
        // generation — the handler never saw the bytes, so a re-issue
        // is safe; the remap/re-handshake underneath the retry carries
        // the fresh generation.
        case TERR_STALE_EPOCH:
            return true;
        default:
            return false;
    }
}

bool DefaultRetryPolicy::DoRetry(const Controller* cntl) const {
    return is_retryable(cntl->ErrorCode());
}

const DefaultRetryPolicy* DefaultRetryPolicy::instance() {
    static const DefaultRetryPolicy p;
    return &p;
}

int Controller::HandleError(CallId id, int error) {
    // Runs with the id locked.
    if (id != current_cid_ && id == unfinished_cid_ && is_retryable(error)) {
        // A connection-level failure of the NON-current in-flight call
        // (the original behind a backup request): only that call dies;
        // the current call may still complete the RPC.
        unfinished_cid_ = INVALID_CALL_ID;
        unfinished_server_id_ = INVALID_VREF_ID;
        if (unfinished_fly_sid_ != INVALID_VREF_ID) {
            Socket::SetFailedById(unfinished_fly_sid_);
            unfinished_fly_sid_ = INVALID_VREF_ID;
        }
        return id_unlock(id);
    }
    if (id == current_cid_ && unfinished_cid_ != INVALID_CALL_ID &&
        is_retryable(error)) {
        // The backup's connection died while the original is still
        // pending: fall back to waiting on the original instead of
        // failing the whole RPC.
        current_cid_ = unfinished_cid_;
        unfinished_cid_ = INVALID_CALL_ID;
        if (current_fly_sid_ != INVALID_VREF_ID) {
            Socket::SetFailedById(current_fly_sid_);
        }
        current_fly_sid_ = unfinished_fly_sid_;
        unfinished_fly_sid_ = INVALID_VREF_ID;
        // The original is current again — restore its server id so
        // EndRPC's final LB feedback (and any wire CANCEL) attributes
        // the verdict to the server actually handling the call, not to
        // the dead backup's.
        current_server_id_ = unfinished_server_id_;
        unfinished_server_id_ = INVALID_VREF_ID;
        backup_won_ = false;  // the backup did NOT complete the RPC
        return id_unlock(id);
    }
    // Cancellation (StartCancel, or the cascade from a canceled upstream
    // server call): tell the server(s) to stop working on the in-flight
    // tries before finishing locally — the whole point of the cascade is
    // that an abandoned call frees CPU all the way down.
    if (error == ECANCELED) {
        canceled_.store(true, std::memory_order_release);
        if (span_ != nullptr) {
            span_->Annotate("canceled: wire CANCEL sent to in-flight tries");
        }
        SendWireCancel();
    }
    // The failing try's dedicated connection is dead weight from here
    // (retry opens a fresh one; terminal failure closes it in EndRPC).
    if (current_fly_sid_ != INVALID_VREF_ID && is_retryable(error)) {
        Socket::SetFailedById(current_fly_sid_);
        current_fly_sid_ = INVALID_VREF_ID;
    }
    const int effective_max_retry =
        max_retry_ >= 0 ? max_retry_
                        : (channel_ ? channel_->options().max_retry : 0);
    FeedbackToLB(error);  // per-try completion (the retry is a new pick)
    // Pluggable retry decision (reference retry_policy.h:28-68): the
    // policy inspects the failed try's error on the controller.
    const RetryPolicy* rp =
        channel_ != nullptr && channel_->options().retry_policy != nullptr
            ? channel_->options().retry_policy
            : DefaultRetryPolicy::instance();
    SetFailed(error, "%s", terror(error));
    if (rp->DoRetry(this) && current_try_ < effective_max_retry &&
        (deadline_us_ == 0 || monotonic_time_us() < deadline_us_)) {
        // Draining peers are a special retry class: the server announced
        // a planned shutdown and provably never processed this try, so
        // re-issuing elsewhere cannot amplify load — it spends NO budget
        // token (the zero-downtime contract: a rolling restart costs no
        // retry budget and trips no breaker).
        const bool budget_free = (error == TERR_DRAINING);
        if (budget_free && span_ != nullptr) {
            span_->Annotate("server draining, re-routed");
        }
        if (budget_free) *g_drain_reroutes << 1;
        // Retry throttling (gRPC-style retry budget, channel.h): under a
        // correlated failure every caller retrying independently is the
        // retry storm that amplifies overload — once the per-channel
        // bucket is dry, fail now with the try's own error instead.
        if (!budget_free && channel_ != nullptr &&
            !channel_->retry_budget().Withdraw()) {
            *g_budget_exhausted << 1;
            if (span_ != nullptr) {
                span_->Annotate(
                    "retry budget exhausted: failing with this try's error");
            }
        } else {
            const CallId next = id_next_version(current_cid_);
            if (next == INVALID_CALL_ID && !budget_free &&
                channel_ != nullptr) {
                // The re-issue never went out: the token goes back.
                channel_->retry_budget().Refund();
            }
            if (next != INVALID_CALL_ID) {
                ++current_try_;
                current_cid_ = next;
                *g_client_retries << 1;
                int64_t backoff_ms = rp->BackoffMs(this);
                // An overloaded server suggested when to come back:
                // honor it with jitter in [s/2, s] — synchronized
                // retries arriving exactly at s would re-create the
                // thundering herd the backoff exists to spread. The
                // policy's own (longer) backoff wins if larger.
                if (error == TERR_OVERLOAD && suggested_backoff_ms_ > 0) {
                    const int64_t s = suggested_backoff_ms_;
                    int64_t jittered =
                        s / 2 + (int64_t)(fast_rand() %
                                          (uint64_t)(s / 2 + 1));
                    // Capped by the call's remaining deadline budget
                    // (ISSUE 15 satellite): a suggestion past the
                    // deadline used to fall through the overshoot
                    // guard below and re-issue IMMEDIATELY at a server
                    // that just said "not now" — hammering it AND
                    // burning the try. Sleep the useful fraction of
                    // what's left (7/8, so the retry itself still has
                    // budget to run) instead.
                    if (deadline_us_ > 0) {
                        const int64_t remaining_ms =
                            (deadline_us_ - monotonic_time_us()) / 1000;
                        const int64_t cap =
                            remaining_ms -
                            std::max<int64_t>(1, remaining_ms / 8);
                        if (jittered > cap) {
                            jittered = std::max<int64_t>(cap, 0);
                            if (span_ != nullptr) {
                                span_->Annotate(
                                    "overload backoff clamped to "
                                    "deadline budget: " +
                                    std::to_string(jittered) +
                                    "ms (server suggested " +
                                    std::to_string(s) + "ms)");
                            }
                        }
                    }
                    backoff_ms = std::max<int64_t>(backoff_ms, jittered);
                }
                error_code_ = 0;  // a later try owns the final verdict
                error_text_.clear();
                if (backoff_ms > 0 &&
                    (deadline_us_ == 0 ||
                     monotonic_time_us() + backoff_ms * 1000 <
                         deadline_us_)) {
                    // Issue after the backoff; the timer holds only the
                    // NEW cid value (stale-safe, like every other timer
                    // here).
                    TimerThread::singleton()->schedule(
                        &Controller::HandleBackoffThunk,
                        (void*)(uintptr_t)current_cid_,
                        monotonic_time_us() + backoff_ms * 1000);
                } else {
                    IssueRPC();
                }
                return id_unlock(id);
            }
        }
    }
    EndRPC(id);
    return 0;
}

// Backoff expiry: re-issue the already-bumped try (the id value alone is
// carried; a completed/canceled RPC makes the lock fail harmlessly).
void Controller::HandleBackoffThunk(void* arg) {
    const CallId cid = (CallId)(uintptr_t)arg;
    void* data = nullptr;
    if (id_lock_range(cid, &data) != 0) return;
    auto* cntl = (Controller*)data;
    if (cid == cntl->current_cid_) {
        cntl->IssueRPC();
    }
    id_unlock(cid);
}

void Controller::FeedbackToLB(int error, int64_t now_us) {
    if (channel_ == nullptr || current_server_id_ == INVALID_VREF_ID) return;
    LoadBalancerWithNaming* lb = channel_->lb();
    if (lb != nullptr) {
        const int64_t try_latency_us =
            (now_us != 0 ? now_us : stage::now_us()) - try_start_us_;
        LoadBalancer::CallInfo info;
        info.server_id = current_server_id_;
        // Per-try latency: charging earlier failed tries' time to the
        // final server would invert locality-aware ranking.
        info.latency_us = try_latency_us;
        info.error_code = error;
        lb->Feedback(info);
        // Circuit breaker: chronic/bursty error rates isolate the server
        // (SetFailed -> health check revives it later with fresh windows;
        // reference Call::OnComplete -> Socket::FeedbackCircuitBreaker).
        SocketUniquePtr s = SocketUniquePtr::FromId(current_server_id_);
        if (s && !s->circuit_breaker().OnCallEnd(error, try_latency_us)) {
            LOG(WARNING) << "circuit breaker isolating "
                         << endpoint2str(s->remote_side()) << " (short "
                         << s->circuit_breaker().short_window_error_percent()
                         << "%, long "
                         << s->circuit_breaker().long_window_error_percent()
                         << "%)";
            s->SetFailedWithError(EHOSTDOWN);
        }
    }
    current_server_id_ = INVALID_VREF_ID;
}

void Controller::IssueRPC() {
    // Stage clock: the first try starts where CallMethod read the clock;
    // a re-issue (retry, backup) reads it anew.
    try_start_us_ = current_try_ == 0 ? start_us_ : stage::now_us();
    SocketUniquePtr s;
    if (channel_->lb() != nullptr) {
        // LB mode: pick a live server, excluding ones tried by earlier
        // attempts of this RPC (reference controller.cpp:1098 SelectServer
        // + ExcludedServers controller.cpp:644-680).
        SelectIn in;
        in.request_code = request_code_;
        in.has_request_code = has_request_code_;
        in.excluded = excluded_;
        SelectOut out;
        const int rc = channel_->lb()->SelectServer(in, &out);
        if (rc != 0) {
            id_error(current_cid_, rc);
            return;
        }
        if (out.skipped_draining) {
            // A draining node was passed over for this pick: visible in
            // stitched traces and countable mesh-wide.
            *g_drain_reroutes << 1;
            if (span_ != nullptr) {
                span_->Annotate("server draining, re-routed");
            }
        }
        if (out.zone_spilled && span_ != nullptr) {
            // Cross-pod spill (ISSUE 14): the local zone could not serve
            // this pick — the counter lives in the zone LB layer, the
            // trace evidence here.
            span_->Annotate("cross-zone spill to " +
                            endpoint2str(out.ptr->remote_side()));
        }
        if (out.skipped_ejected && span_ != nullptr) {
            // An ejected outlier was passed over (ISSUE 20): the note
            // carries WHY ("ejected: latency outlier 8.2x median") so a
            // trace reader sees the routing shift without the portal.
            span_->Annotate(out.outlier_note.empty()
                                ? "outlier ejected, re-routed"
                                : out.outlier_note + ", re-routed");
        }
        if (out.outlier_probe && span_ != nullptr) {
            // This call IS the reinstatement probe for an ejected node.
            span_->Annotate("outlier reinstatement probe to " +
                            endpoint2str(out.ptr->remote_side()));
        }
        s = std::move(out.ptr);
        current_server_id_ = s->id();
        if (excluded_ == nullptr) excluded_ = new ExcludedServers;
        excluded_->Add(s->id());
    } else {
        SocketId sid = channel_->AcquirePinnedSocket();
        if (sid == INVALID_VREF_ID &&
            SocketMap::singleton()->GetOrCreate(
                channel_->server(), Channel::client_messenger(), &sid,
                channel_->transport_tier()) != 0) {
            id_error(current_cid_, TERR_FAILED_SOCKET);
            return;
        }
        single_server_id_ = sid;
        if (Socket::AddressSocket(sid, &s) != 0) {
            id_error(current_cid_, TERR_FAILED_SOCKET);
            return;
        }
    }
    remote_side_ = s->remote_side();

    // Connection selection (reference controller.cpp:1135-1173): pooled
    // and short modes write on a dedicated connection instead of the
    // shared main socket; the main socket still carries LB identity,
    // circuit-breaker state and health checks.
    // Streaming RPCs always ride the shared single connection: the
    // stream binds to the connection that carried the establishing RPC,
    // which must be neither pooled (a later RPC would interleave with
    // stream frames) nor closed at EndRPC (reference streams ride the
    // main socket for the same reason).
    // grpc channels always ride their pinned h2 connection: pooled/short
    // fly sockets come from endpoint-keyed shared pools that tpu_std
    // channels use too, and an h2 session installed there would corrupt
    // the other protocol's traffic (h2 multiplexes concurrent calls on
    // one connection anyway — pooling adds nothing).
    const ConnectionType ct =
        request_stream_ != INVALID_VREF_ID ||
                channel_->options().protocol == "grpc"
            ? CONNECTION_TYPE_SINGLE
            : channel_->options().connection_type;
    if (ct != CONNECTION_TYPE_SINGLE) {
        SocketId fly = INVALID_VREF_ID;
        int rc2;
        // Fly connections inherit the main socket's forced tier: a dcn
        // LB member's pooled/short connections are dcn too (and pool
        // under the (endpoint, tier) key, never mixing with tcp).
        const int fly_tier =
            s->transport() == nullptr ? s->forced_transport_tier() : -1;
        if (ct == CONNECTION_TYPE_POOLED) {
            rc2 = SocketPool::singleton()->Get(s->remote_side(),
                                               Channel::client_messenger(),
                                               &fly, fly_tier);
        } else {  // SHORT: fresh connection, closed after the response
            rc2 = CreateClientSocket(s->remote_side(),
                                     Channel::client_messenger(), &fly,
                                     fly_tier);
        }
        if (rc2 != 0) {
            id_error(current_cid_, TERR_FAILED_SOCKET);
            return;
        }
        SocketUniquePtr fly_ptr;
        if (Socket::AddressSocket(fly, &fly_ptr) != 0) {
            id_error(current_cid_, TERR_FAILED_SOCKET);
            return;
        }
        current_fly_sid_ = fly;
        s = std::move(fly_ptr);
    }

    // Sender-side frame limit: the receiver rejects >256MB frames as a
    // PROTOCOL error (failing the whole connection); catch it here so only
    // this one RPC fails (also guards the uint32 length field).
    if (request_buf_.size() + request_attachment_.size() > (200u << 20)) {
        id_error(current_cid_, TERR_REQUEST);
        return;
    }

    if (channel_->options().protocol == "grpc") {
        // gRPC over h2c: the h2 client session multiplexes this call as
        // a new stream; the response completes the RPC via
        // CompleteClientUnaryResponse (thttp/http2_client.cc). Retry,
        // backup, timeout, and LB machinery above are protocol-agnostic.
        if (span_ != nullptr) {
            span_->sent_us = monotonic_time_us();
        }
        std::string authorization;
        if (channel_->options().auth != nullptr &&
            channel_->options().auth->GenerateCredential(&authorization) !=
                0) {
            id_error(current_cid_, TERR_AUTH);
            return;
        }
        const std::string path = "/" + method_->service()->full_name() +
                                 "/" + method_->name();
        if (H2ClientSendUnary(s.get(), current_cid_, path,
                              endpoint2str(remote_side_), request_buf_,
                              deadline_us_, authorization, tenant_,
                              priority_, session_) != 0) {
            id_error(current_cid_, errno != 0 ? errno : TERR_FAILED_SOCKET);
        }
        return;
    }

    // tpu_std auth fight (reference socket.h:515): the first caller on a
    // fresh connection attaches the credential; concurrent first-writers
    // wait for its outcome instead of re-authenticating. A PREVIOUS try
    // of this RPC that won the fight but died releases it first so this
    // try (or another caller) can re-fight.
    if (auth_fight_sid_ != INVALID_VREF_ID) {
        SocketUniquePtr prev;
        if (Socket::AddressSocket(auth_fight_sid_, &prev) == 0) {
            prev->AbortAuthentication();
        }
        auth_fight_sid_ = INVALID_VREF_ID;
    }
    std::string auth_data;
    bool send_auth = false;
    if (channel_->options().auth != nullptr) {
        while (!s->authenticated()) {
            if (s->FightAuthentication() == 0) {
                if (channel_->options().auth->GenerateCredential(
                        &auth_data) != 0) {
                    s->AbortAuthentication();
                    id_error(current_cid_, TERR_AUTH);
                    return;
                }
                send_auth = true;
                auth_fight_sid_ = s->id();
                break;
            }
            if (s->WaitAuthenticated(deadline_us_) != 0) {
                // Distinguish a dead connection from a slow/wedged
                // authenticator for the caller's diagnosis.
                id_error(current_cid_, s->Failed() ? TERR_FAILED_SOCKET
                                                   : TERR_RPC_TIMEDOUT);
                return;
            }
            // Resolved: either authenticated (loop exits) or the winner
            // aborted (loop re-fights).
        }
    }

    rpc::RpcMeta meta;
    auto* req_meta = meta.mutable_request();
    req_meta->set_service_name(method_->service()->full_name());
    req_meta->set_method_name(method_->name());
    if (deadline_us_ > 0) {
        // Remaining budget, floored at 1ms while any budget truly
        // remains: plain /1000 truncation would stamp a live sub-ms
        // budget as 0, which the server rejects as expired-on-arrival.
        // 0 is reserved for "the deadline has really passed" (the server
        // sheds without executing).
        const int64_t remaining_us = deadline_us_ - monotonic_time_us();
        req_meta->set_timeout_ms(
            remaining_us > 0 ? std::max<int64_t>(1, remaining_us / 1000)
                             : 0);
    }
    if (log_id_ != 0) req_meta->set_log_id(log_id_);
    // QoS identity: resolved (explicit or inherited) by CallMethod; an
    // unset pair costs no meta bytes and the server classes the call as
    // the default tenant/priority.
    if (!tenant_.empty()) req_meta->set_tenant(tenant_);
    if (priority_ >= 0) req_meta->set_priority(priority_);
    // Sticky-session identity (ISSUE 16): named so an L7 front door can
    // pin the whole session to one backend; hop-to-hop like tenant.
    if (!session_.empty()) req_meta->set_session(session_);
    // Pod identity (ISSUE 15d): a zone-tagged sender announces itself
    // so the receiver can price cross-pod spill arrivals above local
    // work (and shed them first within a priority level).
    {
        const std::string my_zone = FLAGS_rpc_zone.get();
        if (!my_zone.empty()) req_meta->set_zone(my_zone);
    }
    if (span_ != nullptr) {
        req_meta->set_trace_id(span_->trace_id);
        req_meta->set_span_id(span_->span_id);
        if (span_->parent_span_id != 0) {
            req_meta->set_parent_span_id(span_->parent_span_id);
        }
        span_->remote_side = remote_side_;
        span_->retries = current_try_;
        if (current_try_ > 0) {
            span_->Annotate("re-issued try " + std::to_string(current_try_) +
                            " to " + endpoint2str(remote_side_));
        }
    }
    meta.set_correlation_id(current_cid_);
    if (send_auth) {
        meta.set_auth_data(auth_data);
    }
    if (request_compress_type_ != COMPRESS_NONE) {
        meta.set_compress_type(request_compress_type_);
    }
    // The wire attachment: the user's inline bytes, plus — when this
    // try's transport tier cannot carry a one-sided reference — the
    // pinned pool bytes appended inline. Eligibility is the Transport
    // seam's verdict (ISSUE 12): an LB that picks a plain-TCP replica
    // for one try of a descriptor-pinned call degrades that try to
    // inline instead of failing it on the server. The common paths (no
    // pinned attachment, or a capable tier) pay no IOBuf copy — the
    // combined buffer is materialized only inside the fallback branch.
    const IOBuf* wire_att = &request_attachment_;
    IOBuf inline_fallback_att;
    // One-sided pool attachment (ISSUE 9): the frame carries ONLY the
    // header + meta (+ inline payload pb); the attachment crosses the
    // seam as a block reference the receiver maps in place. The pin is
    // a lease (released exactly once at EndRPC; reaper/peer-death are
    // the crash backstops). Arm it with this try's identity: owning
    // call id, expiry derived from the propagated RPC deadline, and the
    // socket the descriptor rides — so a SIGKILLed peer releases
    // exactly the pins posted toward it (server_call::OnSocketFailed).
    if (pool_lease_id_ != 0) {
        // Arm is the liveness check AND the re-key, in one registry
        // lock acquisition (a separate Alive() probe would leave a
        // window where reclamation lands between check and arm). A
        // false return means the pin was reclaimed underneath us
        // (lease expired, or a previous try's peer died and took the
        // pin with it): the referenced bytes may already be recycled,
        // and the ONLY copy of the payload was that block — so every
        // subsequent try must keep failing with the stale-reference
        // error (lease id deliberately NOT cleared: a later try that
        // silently framed without the attachment would hand the
        // server an empty payload and report success — data loss).
        // Bounded by max_retry/deadline like any other retriable
        // failure; the terminal error is TERR_STALE_EPOCH.
        // A backup re-issue ADDS this try's socket to the lease's
        // entitled peers (the original try — still in flight — may be
        // mid-read on its own socket); a plain retry replaces it.
        const bool backup_in_flight =
            unfinished_cid_ != INVALID_CALL_ID;
        if (!block_lease::Arm(pool_lease_id_, (uint64_t)correlation_id_,
                              deadline_us_, (uint64_t)s->id(),
                              backup_in_flight)) {
            id_error(current_cid_, TERR_STALE_EPOCH);
            return;
        }
        if (TransportDescriptorCapable(s.get())) {
            // Re-issues restamp the CURRENT pool generation: the pin
            // (and its offset) is still valid — the lease holds it — so
            // a retry after a TERR_STALE_EPOCH re-handshake carries the
            // epoch the receiver's fresh mapping expects.
            pool_attachment_.pool_epoch = IciBlockPool::pool_epoch();
            auto* pd = meta.mutable_pool_attachment();
            pd->set_pool_id(pool_attachment_.pool_id);
            pd->set_offset(pool_attachment_.offset);
            pd->set_length(pool_attachment_.length);
            pd->set_crc32c(pool_attachment_.crc32c);
            pd->set_pool_epoch(pool_attachment_.pool_epoch);
            *g_pool_desc_sends << 1;
            *g_pool_desc_bytes << (int64_t)pool_attachment_.length;
            transport_stats::AddDescOut(s->transport_tier(),
                                        (int64_t)pool_attachment_.length);
        } else {
            // Descriptor-incapable tier for THIS try: the Arm above
            // proved the pin (and therefore the stashed view) is still
            // live, so the bytes go inline — the payload arrives either
            // way, the zero-copy win is simply unavailable on this
            // transport.
            inline_fallback_att.append(request_attachment_);
            inline_fallback_att.append(pool_attachment_.data,
                                       pool_attachment_.length);
            wire_att = &inline_fallback_att;
            *g_pool_desc_wire_fallbacks << 1;
        }
    }
    meta.set_attachment_size((uint32_t)wire_att->size());
    if (FLAGS_rpc_checksum.get()) {
        uint32_t crc = crc32c_iobuf(0, request_buf_);
        crc = crc32c_iobuf(crc, *wire_att);
        meta.set_body_checksum(crc);
    }
    if (request_stream_ != INVALID_VREF_ID) {
        auto* ss = meta.mutable_stream_settings();
        ss->set_stream_id(request_stream_);
        ss->set_window_size(request_stream_window_);
    } else if (push_open_id_ != 0 && !has_push_open_) {
        // push_stream open/resume (ISSUE 17): client side only —
        // has_push_open_ means this Controller is serving a push open,
        // not issuing one.
        auto* ss = meta.mutable_stream_settings();
        ss->set_stream_id(push_open_id_);
        ss->set_version(push_stream::kStreamVersion);
        ss->set_rx_window(push_open_rx_window_);
        ss->set_resume_from_seq(push_open_resume_from_);
        ss->set_push(true);
    }
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, request_buf_, *wire_att);
    // The request is enqueued: one clock read ends trpc.issue, starts
    // tnet.write_queue (the socket's writer ends it at the post) and is
    // the rpcz sent phase.
    const int64_t enqueued_us = stage::now_us();
    stage::Add(stage::kIssue, enqueued_us - try_start_us_);
    if (span_ != nullptr) {
        span_->request_bytes = (int64_t)frame.size();
        span_->sent_us = enqueued_us;
    }
    if (s->Write(&frame, current_cid_, enqueued_us) != 0) {
        // Queue full or failed socket: deliver the error (may retry).
        id_error(current_cid_, errno != 0 ? errno : TERR_FAILED_SOCKET);
    }
}

void* Controller::RunDoneThunk(void* arg) {
    ((google::protobuf::Closure*)arg)->Run();
    return nullptr;
}

// ---------------- backup requests ----------------

// Timer callback: holds only the base CallId VALUE (a finished RPC makes
// the lock fail — same hazard discipline as HandleTimeoutCb).
void Controller::HandleBackupThunk(void* arg) {
    const CallId cid = (CallId)(uintptr_t)arg;
    void* data = nullptr;
    if (id_lock_range(cid, &data) != 0) {
        return;  // RPC already completed
    }
    ((Controller*)data)->MaybeIssueBackup();
    id_unlock(cid);
}

void Controller::MaybeIssueBackup() {
    // Runs with the id locked.
    if (Failed() || canceled_ || unfinished_cid_ != INVALID_CALL_ID) {
        return;  // already failed / already one backup out
    }
    if (channel_ != nullptr &&
        channel_->options().backup_request_policy != nullptr &&
        !channel_->options().backup_request_policy->DoBackup(this)) {
        return;  // the policy vetoed hedging this call
    }
    const int effective_max_retry =
        max_retry_ >= 0 ? max_retry_
                        : (channel_ ? channel_->options().max_retry : 0);
    if (current_try_ >= effective_max_retry) {
        return;  // backup consumes retry budget (reference semantics)
    }
    // Hedging is a re-issue too: an exhausted retry budget vetoes the
    // backup (under overload, doubling the traffic is the last thing the
    // fleet needs — same rationale as the retry path).
    if (channel_ != nullptr && !channel_->retry_budget().Withdraw()) {
        *g_budget_exhausted << 1;
        if (span_ != nullptr) {
            span_->Annotate("retry budget exhausted: backup request vetoed");
        }
        return;
    }
    const CallId next = id_next_version(current_cid_);
    if (next == INVALID_CALL_ID) {
        if (channel_ != nullptr) channel_->retry_budget().Refund();
        return;
    }
    // The original call STAYS live (ranged id): record it so its response
    // can still win and its socket errors fail only it. Feed the LB a
    // slow-but-ok data point for the original's server (elapsed latency,
    // no error — the locality-aware policy deprioritizes it; the breaker
    // sees no failure). The winner's stats land in EndRPC.
    unfinished_cid_ = current_cid_;
    unfinished_fly_sid_ = current_fly_sid_;
    current_fly_sid_ = INVALID_VREF_ID;
    // Save the original's server BEFORE the feedback clears
    // current_server_id_: the loser-cancel at EndRPC (and the fall-back
    // when the backup's connection dies) still needs its address.
    unfinished_server_id_ = current_server_id_;
    FeedbackToLB(0);
    current_cid_ = next;
    ++current_try_;
    backup_issued_ = true;
    *g_client_backups << 1;
    IssueRPC();
}

// Pooled mode returns response-delivering connections to the pool; every
// other pooled/short connection of this RPC (abandoned original behind a
// winning backup, timed-out try, short-lived conn) is closed — it may
// carry an orphan in-flight response and must never serve another call.
void Controller::ReleaseFlySockets() {
    if (channel_ == nullptr) return;
    const ConnectionType ct = channel_->options().connection_type;
    if (ct == CONNECTION_TYPE_SINGLE) return;
    if (reusable_fly_sid_ != INVALID_VREF_ID) {
        if (ct == CONNECTION_TYPE_POOLED) {
            SocketPool::singleton()->Return(reusable_fly_sid_);
        } else {
            Socket::SetFailedById(reusable_fly_sid_);
        }
        reusable_fly_sid_ = INVALID_VREF_ID;
    }
    if (current_fly_sid_ != INVALID_VREF_ID) {
        Socket::SetFailedById(current_fly_sid_);
        current_fly_sid_ = INVALID_VREF_ID;
    }
    if (unfinished_fly_sid_ != INVALID_VREF_ID) {
        Socket::SetFailedById(unfinished_fly_sid_);
        unfinished_fly_sid_ = INVALID_VREF_ID;
    }
}

void Controller::EndRPC(CallId locked_id) {
    // One clock read for the completion seam: the call's latency, the
    // rpcz end phase, the LB's per-try latency and trpc.match (reply
    // parsed -> here; a synchronous caller is signalled at the end of
    // this function, and CallMethod takes trpc.caller_wake from here).
    const int64_t end_us = stage::now_us();
    latency_us_ = end_us - start_us_;
    if (reply_parsed_us_ != 0) {
        stage::Add(stage::kMatch, end_us - reply_parsed_us_);
        reply_parsed_us_ = 0;
    }
    // One-sided completion (ISSUE 9/10): the response (or terminal
    // failure) means the peer will never again read our posted
    // descriptor — release the lease, returning the pinned block to the
    // owner's pool. Exactly-once even across retry/backup re-issues and
    // against the reaper/peer-death reclamation paths (block_lease.h).
    ReleasePoolLease();
    pool_attachment_ = PoolAttachment();
    // The RPC is over: an unfired NotifyOnCancel closure runs now
    // (protobuf contract — exactly once whether or not canceled).
    RunCancelClosure();
    // A success refills the retry budget by the configured ratio (the
    // gRPC token-bucket shape: sustained failure drains it, recovery
    // earns re-issue capacity back).
    if (channel_ != nullptr && error_code_ == 0) {
        channel_->retry_budget().OnSuccess();
    }
    // A failed auth-carrying call releases the fight it won (success
    // paths already resolved it via SetAuthenticated on the response).
    if (auth_fight_sid_ != INVALID_VREF_ID) {
        if (Failed()) {
            SocketUniquePtr s;
            if (Socket::AddressSocket(auth_fight_sid_, &s) == 0) {
                s->AbortAuthentication();
            }
        }
        auth_fight_sid_ = INVALID_VREF_ID;
    }
    // Hedge loser cancel (ISSUE 16): the RPC completed but the OTHER try
    // is still live on its server — a wire CANCEL stops that server from
    // burning CPU on a call nobody waits for, and lets it ack/release any
    // descriptor lease the abandoned try carried. Skip when the whole RPC
    // was canceled (SendWireCancel already covered both tries).
    if (unfinished_cid_ != INVALID_CALL_ID &&
        !canceled_.load(std::memory_order_relaxed) && channel_ != nullptr) {
        SocketId sid = unfinished_fly_sid_;
        if (sid == INVALID_VREF_ID) sid = unfinished_server_id_;
        if (sid == INVALID_VREF_ID) sid = single_server_id_;
        if (sid != INVALID_VREF_ID) {
            if (channel_->options().protocol == "grpc") {
                H2ClientCancel(sid, unfinished_cid_);
            } else {
                SendTpuStdCancel(sid, unfinished_cid_);
            }
        }
    }
    ReleaseFlySockets();
    if (span_ != nullptr) {
        if (error_code_ != 0) {
            // The terminal verdict rides the span so a stitched timeline
            // shows WHY a hop died (shed, expired, canceled, refused)
            // even when the downstream produced no span of its own.
            span_->Annotate("failed: " + error_text_);
            if (FLAGS_chaos_enabled.get()) {
                span_->Annotate("note: local chaos injection is enabled");
            }
        }
        span_->end_us = end_us;
        span_->error_code = error_code_;
        Collector::singleton()->submit(span_);
        span_ = nullptr;
    }
    FeedbackToLB(error_code_, end_us);
    // A client stream that never got bound to a connection must be failed
    // here — EndRPC is the single funnel every termination path (success
    // without stream settings, server error, timeout, socket failure)
    // passes through, so the stream's creation/rx refs can't leak.
    if (request_stream_ != INVALID_VREF_ID && !request_stream_bound_) {
        stream_internal::FailStream(request_stream_);
    }
    if (timeout_timer_ != INVALID_TIMER_ID) {
        // Best-effort: if the callback is running it will find the id
        // destroyed (it only holds the id VALUE, never this pointer).
        TimerThread::singleton()->unschedule(timeout_timer_, false);
        timeout_timer_ = INVALID_TIMER_ID;
    }
    if (backup_timer_ != INVALID_TIMER_ID) {
        TimerThread::singleton()->unschedule(backup_timer_, false);
        backup_timer_ = INVALID_TIMER_ID;
    }
    google::protobuf::Closure* done = done_;
    id_unlock_and_destroy(locked_id);
    // `this` may be deleted by done from here on.
    if (done != nullptr) {
        if (is_running_on_fiber_worker()) {
            done->Run();
        } else {
            // Never run user code on the timer thread.
            fiber_t tid;
            if (fiber_start_background(&tid, nullptr, RunDoneThunk, done) !=
                0) {
                done->Run();
            }
        }
    }
}

// ---------------- client response path ----------------

void ProcessTpuStdResponse(TpuStdMessage* msg, const rpc::RpcMeta& meta) {
    const CallId cid = meta.correlation_id();
    // The reply is cut and its meta parsed: one clock read ends
    // tnet.consume_to_cut, starts trpc.match and is the rpcz received
    // phase.
    const int64_t parsed_us = stage::now_us();
    if (msg->consumed_us != 0) {
        stage::Add(stage::kConsumeToCut, parsed_us - msg->consumed_us);
    }
    // A dropped response that carried a pool descriptor still acks: the
    // server pinned a block for us, and nobody will ever resolve this
    // copy of the reference — without the ack the pin would sit until
    // the deadline-derived reaper. Covers the finished-RPC and
    // abandoned-try drops below (a late response behind a timeout or a
    // backup winner is exactly descriptor-heavy load's common case).
    const auto ack_dropped_descriptor = [&] {
        if (meta.response().has_pool_attachment()) {
            SendTpuStdDescAck(msg->socket_id, cid,
                              meta.response().pool_attachment()
                                  .ack_token());
        }
    };
    void* data = nullptr;
    // Ranged lock: with a backup request out, TWO versions are in flight
    // and either response may win. Versions outside the live set (retried
    // tries, duplicates, finished RPCs) are dropped below / by the lock.
    if (id_lock_range(cid, &data) != 0) {
        // destroyed (finished) or stale beyond the range: drop
        ack_dropped_descriptor();
        return;
    }
    Controller* cntl = (Controller*)data;
    if (cid != cntl->current_cid_ && cid != cntl->unfinished_cid_) {
        id_unlock(cid);  // an abandoned try's late response
        ack_dropped_descriptor();
        return;
    }
    // Hedge winner normalization (ISSUE 16): whichever live try delivered
    // THIS response is the winner — relabel it "current" so every
    // termination path below (fly-sid reuse, LB feedback, the loser
    // cancel at EndRPC) uniformly treats "unfinished" as the loser.
    if (cid == cntl->unfinished_cid_) {
        std::swap(cntl->current_cid_, cntl->unfinished_cid_);
        std::swap(cntl->current_fly_sid_, cntl->unfinished_fly_sid_);
        std::swap(cntl->current_server_id_, cntl->unfinished_server_id_);
    } else if (cntl->unfinished_cid_ != INVALID_CALL_ID) {
        // The BACKUP try's response is completing the RPC (cleared again
        // in HandleError if this response is a retryable error and the
        // call falls back to the still-live original).
        cntl->backup_won_ = true;
    }
    cntl->reply_parsed_us_ = parsed_us;
    if (cntl->span_ != nullptr) {
        cntl->span_->received_us = parsed_us;
        cntl->span_->response_bytes = (int64_t)msg->body.size();
    }
    // Pooled/short: the connection that delivered THIS response is clean
    // (no orphan response pending) and may be pooled again at EndRPC.
    if (cid == cntl->current_cid_ &&
        cntl->current_fly_sid_ != INVALID_VREF_ID) {
        cntl->reusable_fly_sid_ = cntl->current_fly_sid_;
        cntl->current_fly_sid_ = INVALID_VREF_ID;
    } else if (cid == cntl->unfinished_cid_ &&
               cntl->unfinished_fly_sid_ != INVALID_VREF_ID) {
        cntl->reusable_fly_sid_ = cntl->unfinished_fly_sid_;
        cntl->unfinished_fly_sid_ = INVALID_VREF_ID;
    }
    const auto& rmeta = meta.response();
    flight::Record(flight::kRpcRespRecv, cid, (uint64_t)rmeta.error_code());
    // Any NON-auth-error response proves the server accepted this
    // connection's credential: release the auth-fight waiters (a bad
    // credential fails the connection instead, waking them with an
    // error).
    if (rmeta.error_code() != TERR_AUTH) {
        SocketUniquePtr rs;
        if (Socket::AddressSocket(msg->socket_id, &rs) == 0 &&
            !rs->authenticated()) {
            rs->SetAuthenticated("");
        }
    }
    if (rmeta.error_code() != 0) {
        // An error response never hands user code the descriptor view:
        // ack a piggybacked response pool attachment NOW so the server's
        // pin frees without waiting for the reaper (satellite-1 audit —
        // these terminal paths used to strand the pin).
        ack_dropped_descriptor();
        if (rmeta.error_code() == TERR_OVERLOAD ||
            rmeta.error_code() == TERR_OVERCROWDED ||
            rmeta.error_code() == TERR_STALE_EPOCH) {
            // The handler never ran — a priority-aware shed, a socket
            // too crowded to enqueue the work, or an epoch fence
            // refusing a stale zero-copy reference. Route through the
            // ERROR funnel (we hold the id lock — HandleError's
            // contract) so the standard retry machinery applies: budget
            // token spent, backoff honored, LB re-selects via
            // ExcludedServers; a stale-epoch re-issue re-arms the lease
            // and restamps the current pool generation. Without the
            // OVERCROWDED arm a server-side pushback that is_retryable
            // says to retry was terminal anyway — a degraded node's
            // refusals became lost completions instead of re-routes.
            if (rmeta.error_code() == TERR_OVERLOAD &&
                rmeta.has_backoff_ms()) {
                cntl->set_suggested_backoff_ms(rmeta.backoff_ms());
            }
            cntl->HandleError(cid, rmeta.error_code());
            return;
        }
        cntl->SetFailed(rmeta.error_code(), "%s", rmeta.error_text().c_str());
        cntl->EndRPC(cid);
        return;
    }
    if (meta.has_body_checksum() &&
        crc32c_iobuf(0, msg->body) != meta.body_checksum()) {
        ack_dropped_descriptor();  // corrupt response: view never taken
        cntl->SetFailed(TERR_RESPONSE, "response body checksum mismatch");
        cntl->EndRPC(cid);
        return;
    }
    // Split payload/attachment and deserialize.
    const uint32_t att_size = meta.attachment_size();
    if ((size_t)att_size > msg->body.size()) {
        ack_dropped_descriptor();  // malformed response: view never taken
        cntl->SetFailed(TERR_RESPONSE, "attachment_size %u > body %zu",
                        att_size, msg->body.size());
        cntl->EndRPC(cid);
        return;
    }
    IOBuf payload;
    msg->body.cutn(&payload, msg->body.size() - att_size);
    cntl->response_attachment().clear();
    cntl->response_attachment().swap(msg->body);
    if (meta.compress_type() != COMPRESS_NONE) {
        IOBuf raw;
        if (!DecompressBody(meta.compress_type(), payload, &raw)) {
            ack_dropped_descriptor();  // failing call: view never taken
            cntl->SetFailed(TERR_RESPONSE, "decompress response failed");
            cntl->EndRPC(cid);
            return;
        }
        payload.swap(raw);
    }
    // Response-direction descriptor (ISSUE 12): the server answered with
    // a reference into ITS registered pool — resolve it against the
    // mapping this connection's handshake made of that pool, fence the
    // epoch, verify the crc, and hand user code the in-place view with
    // zero inline payload bytes. Scope is the Transport seam's verdict:
    // only the handshake-mapped pool (or our own, on an in-process
    // link) resolves. Every never-will-read path acks immediately so
    // the server's pin frees without waiting for the reaper.
    if (rmeta.has_pool_attachment()) {
        const auto& pd = rmeta.pool_attachment();
        SocketUniquePtr ds;
        const bool have_sock =
            Socket::AddressSocket(msg->socket_id, &ds) == 0;
        const char* pool_base = nullptr;
        size_t pool_size = 0;
        uint64_t map_epoch = 0;
        if (!have_sock ||
            !TransportDescriptorScopeOk(ds.get(), pd.pool_id()) ||
            !pool_registry::Resolve(pd.pool_id(), &pool_base, &pool_size,
                                    &map_epoch) ||
            pd.offset() > pool_size ||
            pd.length() > pool_size - pd.offset()) {
            rsp_desc::CountReject();
            SendTpuStdDescAck(msg->socket_id, cid, pd.ack_token());
            cntl->SetFailed(TERR_RESPONSE,
                            "unresolvable response pool descriptor "
                            "(server pool not mapped on this link, or "
                            "out of bounds)");
            cntl->EndRPC(cid);
            return;
        }
        // Epoch fence BEFORE the crc read — the symmetric twin of the
        // request direction: a stale generation may point at recycled
        // bytes; fail ONLY this call with the retriable error (the
        // re-handshake under the retry refreshes the mapping).
        if (pd.has_pool_epoch() && pd.pool_epoch() != 0 &&
            pd.pool_epoch() != map_epoch) {
            rsp_desc::CountReject();
            SendTpuStdDescAck(msg->socket_id, cid, pd.ack_token());
            cntl->HandleError(cid, TERR_STALE_EPOCH);
            return;
        }
        if (pd.has_crc32c() &&
            crc32c_extend(0, pool_base + pd.offset(), pd.length()) !=
                pd.crc32c()) {
            rsp_desc::CountReject();
            SendTpuStdDescAck(msg->socket_id, cid, pd.ack_token());
            cntl->SetFailed(TERR_RESPONSE,
                            "response pool descriptor crc32c mismatch");
            cntl->EndRPC(cid);
            return;
        }
        Controller::PoolAttachment view;
        view.data = pool_base + pd.offset();
        view.length = pd.length();
        view.pool_id = pd.pool_id();
        view.offset = pd.offset();
        view.crc32c = pd.crc32c();
        view.pool_epoch = pd.pool_epoch();
        view.ack_token = pd.ack_token();
        cntl->SetResponsePoolAttachmentView(view, msg->socket_id, cid);
        rsp_desc::CountResolve((int64_t)pd.length());
        // The logical bytes are this connection's data-plane
        // throughput even though they never crossed the fd/ring.
        ds->add_descriptor_bytes_read((int64_t)pd.length());
        transport_stats::AddDescIn(ds->transport_tier(),
                                   (int64_t)pd.length());
    }
    if (cntl->response_ != nullptr &&
        !ParsePbFromIOBuf(cntl->response_, payload)) {
        cntl->SetFailed(TERR_RESPONSE, "parse response failed");
    }
    // Stream establishment: the server accepted (its settings ride the
    // response meta) — bind the client stream to this connection. Any
    // not-bound stream (including the early-return error paths above) is
    // failed centrally by EndRPC.
    if (cntl->request_stream() != INVALID_VREF_ID && !cntl->Failed() &&
        meta.has_stream_settings()) {
        if (stream_internal::ConnectClientStream(
                cntl->request_stream(), msg->socket_id,
                meta.stream_settings().stream_id(),
                meta.stream_settings().window_size()) == 0) {
            cntl->set_request_stream_bound();
        }
    }
    cntl->EndRPC(cid);
}

void CompleteClientUnaryResponse(uint64_t cid, int error_code,
                                 const std::string& error_text,
                                 IOBuf* payload_pb) {
    void* data = nullptr;
    if (id_lock_range(cid, &data) != 0) {
        return;  // finished or stale beyond the live range: drop
    }
    Controller* cntl = (Controller*)data;
    if (cid != cntl->current_cid_ && cid != cntl->unfinished_cid_) {
        id_unlock(cid);  // an abandoned try's late response
        return;
    }
    // Hedge winner normalization — the h2 twin of the tpu_std path.
    if (cid == cntl->unfinished_cid_) {
        std::swap(cntl->current_cid_, cntl->unfinished_cid_);
        std::swap(cntl->current_fly_sid_, cntl->unfinished_fly_sid_);
        std::swap(cntl->current_server_id_, cntl->unfinished_server_id_);
    } else if (cntl->unfinished_cid_ != INVALID_CALL_ID) {
        cntl->backup_won_ = true;
    }
    if (cntl->span_ != nullptr) {
        cntl->span_->received_us = monotonic_time_us();
        cntl->span_->response_bytes =
            payload_pb != nullptr ? (int64_t)payload_pb->size() : 0;
    }
    if (cid == cntl->current_cid_ &&
        cntl->current_fly_sid_ != INVALID_VREF_ID) {
        cntl->reusable_fly_sid_ = cntl->current_fly_sid_;
        cntl->current_fly_sid_ = INVALID_VREF_ID;
    } else if (cid == cntl->unfinished_cid_ &&
               cntl->unfinished_fly_sid_ != INVALID_VREF_ID) {
        cntl->reusable_fly_sid_ = cntl->unfinished_fly_sid_;
        cntl->unfinished_fly_sid_ = INVALID_VREF_ID;
    }
    if (error_code != 0) {
        cntl->SetFailed(error_code, "%s", error_text.c_str());
    } else if (cntl->response_ != nullptr && payload_pb != nullptr &&
               !ParsePbFromIOBuf(cntl->response_, *payload_pb)) {
        cntl->SetFailed(TERR_RESPONSE, "parse response failed");
    }
    cntl->EndRPC(cid);
}

}  // namespace tpurpc
