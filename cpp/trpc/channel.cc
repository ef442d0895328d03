#include "trpc/channel.h"

#include <cstring>

#include "tbase/errno.h"
#include "tbase/fast_rand.h"
#include "tbase/flight_recorder.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tfiber/call_id.h"
#include "tici/shm_link.h"
#include "tnet/tls.h"
#include "tnet/transport.h"
#include "trpc/lb_with_naming.h"
#include "trpc/controller.h"
#include "trpc/pb_compat.h"
#include "trpc/retry_policy.h"
#include "trpc/compress.h"
#include "trpc/policy_tpu_std.h"
#include "trpc/server_call.h"
#include "trpc/span.h"
#include "trpc/stream.h"
#include "tvar/stage_recorder.h"

#include "tbase/flags.h"

// Default retry budget (gRPC retry-throttling shape; channel.h
// ChannelOptions::retry_budget_*): the burst bounds re-issues under a
// correlated failure, the ratio lets healthy traffic earn them back.
// tokens <= 0 disables throttling process-wide.
DEFINE_int32(rpc_retry_budget_tokens, 100,
             "per-channel retry/backup burst tokens (<=0 disables)");
DEFINE_double(rpc_retry_budget_ratio, 0.1,
              "retry budget tokens earned back per successful RPC");

namespace tpurpc {

Channel::~Channel() = default;

void Channel::ConfigureRetryBudget() {
    const int64_t tokens = options_.retry_budget_tokens >= 0
                               ? options_.retry_budget_tokens
                               : FLAGS_rpc_retry_budget_tokens.get();
    const double ratio = options_.retry_budget_ratio >= 0
                             ? options_.retry_budget_ratio
                             : FLAGS_rpc_retry_budget_ratio.get();
    retry_budget_.Configure(tokens, ratio);
}

InputMessenger* Channel::client_messenger() {
    static InputMessenger* m = [] {
        GlobalInitializeOrDie();
        return new InputMessenger(
            {TpuStdProtocolIndex(), stream_internal::StreamProtocolIndex()});
    }();
    return m;
}

int Channel::Init(const EndPoint& server, const ChannelOptions* options) {
    GlobalInitializeOrDie();
    server_ep_ = server;
    if (options != nullptr) options_ = *options;
    ConfigureRetryBudget();
    // Resolve the transport-tier name once (ISSUE 14): every connection
    // this channel draws — pinned, SocketMap-shared, pooled or short —
    // is created and keyed on this tier.
    if (!options_.transport.empty()) {
        forced_tier_ = FindTransportTier(options_.transport.c_str());
        if (forced_tier_ < 0 && options_.transport == "dcn") {
            forced_tier_ = TierDcn();  // built-in, registered on demand
        }
        if (forced_tier_ < 0) {
            LOG(ERROR) << "unknown ChannelOptions::transport '"
                       << options_.transport << "'";
            return -1;
        }
    }
    // grpc/redis and TLS channels pin their OWN connection: the
    // endpoint-keyed SocketMap/SocketPool sockets are shared with
    // tpu_std channels, and installing an h2/redis session (or a TLS
    // wrap) on a shared socket would corrupt the other protocol's
    // traffic to the same server. pin_connection opts into the same
    // ownership for plain tpu_std (per-channel connections that shard
    // across the epoll loops — load generators, ISSUE 7).
    if (options_.tls || options_.protocol == "grpc" ||
        options_.protocol == "redis" || options_.pin_connection) {
        if (options_.tls && !TlsAvailable()) {
            LOG(ERROR) << "ChannelOptions::tls set but libssl is missing";
            return -1;
        }
        if (CreateOwnedPinnedSocket(&pinned_socket_) != 0) return -1;
        owns_pinned_ = true;
    }
    return 0;
}

int Channel::CreateOwnedPinnedSocket(SocketId* sid) {
    SocketOptions sopts;
    sopts.fd = -1;  // connect-on-first-write
    sopts.remote_side = server_ep_;
    sopts.on_edge_triggered_events = &InputMessenger::OnNewMessages;
    sopts.user = client_messenger();
    if (options_.tls) {
        sopts.tls = true;
        sopts.tls_alpn = options_.protocol == "grpc" ? "h2" : "";
        sopts.tls_sni = options_.tls_sni;
    }
    sopts.forced_transport_tier = forced_tier_;
    if (Socket::Create(sopts, sid) != 0) {
        LOG(ERROR) << "pinned client socket creation failed";
        return -1;
    }
    return 0;
}

SocketId Channel::AcquirePinnedSocket() {
    const SocketId sid = pinned_socket_;
    if (sid == INVALID_VREF_ID) return sid;
    {
        SocketUniquePtr probe;
        if (Socket::AddressSocket(sid, &probe) == 0) {
            // A DRAINING pin (peer sent GOAWAY) is replaced like a dead
            // one — but only for channel-owned pins: the old connection
            // stays alive so its in-flight streams complete; it dies
            // when the drained server closes it.
            if (!owns_pinned_ || !probe->Draining()) return sid;  // live
        }
    }
    if (!owns_pinned_) return sid;  // caller's socket: its death is final
    std::lock_guard<std::mutex> g(pin_mu_);
    // Re-check: another fiber may have recreated while we waited.
    if (pinned_socket_ != sid) return pinned_socket_;
    SocketId fresh;
    if (CreateOwnedPinnedSocket(&fresh) != 0) return pinned_socket_;
    pinned_socket_ = fresh;
    return fresh;
}

int Channel::Init(const char* server_addr_and_port,
                  const ChannelOptions* options) {
    EndPoint ep;
    if (hostname2endpoint(server_addr_and_port, &ep) != 0) {
        LOG(ERROR) << "bad address: " << server_addr_and_port;
        return -1;
    }
    return Init(ep, options);
}

int Channel::InitWithSocketId(SocketId sid, const ChannelOptions* options) {
    GlobalInitializeOrDie();
    if (options != nullptr) options_ = *options;
    ConfigureRetryBudget();
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) != 0) {
        LOG(ERROR) << "InitWithSocketId: dead socket id=" << sid;
        return -1;
    }
    server_ep_ = s->remote_side();
    pinned_socket_ = sid;
    return 0;
}

int Channel::InitIci(const EndPoint& server, const ChannelOptions* options) {
    GlobalInitializeOrDie();
    SocketId sid;
    if (IciConnect(server, client_messenger(), &sid) != 0) {
        LOG(ERROR) << "InitIci: handshake with " << endpoint2str(server)
                   << " failed";
        return -1;
    }
    return InitWithSocketId(sid, options);
}

int Channel::Init(const char* naming_url, const char* lb_name,
                  const ChannelOptions* options) {
    GlobalInitializeOrDie();
    if (options != nullptr) options_ = *options;
    ConfigureRetryBudget();
    // Plain "ip:port" with an LB name degenerates to single-server.
    if (strstr(naming_url, "://") == nullptr) {
        return Init(naming_url, options);
    }
    auto lb = std::make_shared<LoadBalancerWithNaming>();
    if (lb->Init(naming_url, lb_name == nullptr ? "rr" : lb_name) != 0) {
        return -1;
    }
    lb_ = std::move(lb);
    return 0;
}

// Timer callback for RPC deadlines: holds only the CallId VALUE (never a
// pointer), so a finished/destroyed RPC makes this a no-op (reference
// HandleTimeout, controller.cpp:593).
static void HandleTimeoutCb(void* arg) {
    id_error((CallId)(uintptr_t)arg, TERR_RPC_TIMEDOUT);
}

void Channel::CallMethod(const google::protobuf::MethodDescriptor* method,
                         google::protobuf::RpcController* controller,
                         const google::protobuf::Message* request,
                         google::protobuf::Message* response,
                         google::protobuf::Closure* done) {
    Controller* cntl = static_cast<Controller*>(controller);
    cntl->channel_ = this;
    cntl->method_ = method;
    cntl->response_ = response;
    cntl->done_ = done;
    cntl->start_us_ = stage::now_us();  // trpc.issue starts here

    if (id_create(&cntl->correlation_id_, cntl,
                  &Controller::HandleErrorThunk) != 0) {
        cntl->SetFailed(TERR_INTERNAL, "id_create failed");
        // This path never reaches EndRPC (there is no id to destroy), so
        // release any pre-attached client stream here.
        if (cntl->request_stream() != INVALID_VREF_ID) {
            stream_internal::FailStream(cntl->request_stream());
        }
        if (done) done->Run();
        return;
    }
    cntl->current_cid_ = cntl->correlation_id_;

    // Hold the id lock through setup + IssueRPC (reference CallMethod does
    // the same, channel.cpp:467): an early timeout/error gets QUEUED on the
    // locked id and delivered at unlock, instead of destroying the
    // Controller under our feet mid-issue.
    const CallId cid = cntl->correlation_id_;
    void* unused;
    CHECK_EQ(id_lock(cid, &unused), 0);

    // rpcz: a call issued inside a sampled server handler CONTINUES the
    // upstream trace (cross-host stitching needs the parent link — the
    // downstream hop's server span points back at THIS client span);
    // outside a handler the local sampling gate may start a fresh trace.
    // Contract (same as the deadline-inheritance deref below): the
    // upstream controller — and thus its span — is valid only until the
    // handler runs done->Run(); a handler must not issue calls under
    // this scope after completing its own response.
    Controller* up = CurrentServerCall();
    Span* upspan = up != nullptr && IsRpczEnabled() ? up->span_ : nullptr;
    if (upspan != nullptr || IsRpczSampled()) {
        auto* span = new Span;
        span->kind = Span::CLIENT;
        if (upspan != nullptr) {
            span->trace_id = upspan->trace_id;
            span->parent_span_id = upspan->span_id;
        } else {
            span->trace_id = fast_rand();
        }
        span->span_id = fast_rand();
        span->method = method->full_name();
        span->start_us = cntl->start_us_;
        cntl->span_ = span;
        cntl->sampled_trace_id_ = span->trace_id;
    }

    if (!SerializePbToIOBuf(*request, &cntl->request_buf_)) {
        cntl->SetFailed(TERR_REQUEST, "serialize request failed");
        cntl->EndRPC(cid);
        return;
    }
    // gRPC framing carries its own compressed-flag + grpc-encoding
    // negotiation, which this client doesn't speak yet — sending our
    // gzip bytes with flag 0 would make the server parse gzip as raw pb.
    // Fail loudly instead of corrupting.
    if (options_.protocol == "grpc" &&
        cntl->request_compress_type() != COMPRESS_NONE) {
        cntl->SetFailed(TERR_REQUEST,
                        "request compression unsupported on grpc channels");
        cntl->EndRPC(cid);
        return;
    }
    // Compress ONCE here, not per-try: retries and backups re-send the
    // same compressed bytes (reference compresses in CallMethod too).
    if (cntl->request_compress_type() != COMPRESS_NONE) {
        IOBuf compressed;
        if (!CompressBody(cntl->request_compress_type(),
                          cntl->request_buf_, &compressed)) {
            cntl->SetFailed(TERR_REQUEST, "compress request failed");
            cntl->EndRPC(cid);
            return;
        }
        cntl->request_buf_.swap(compressed);
    }

    const int64_t timeout_ms =
        cntl->timeout_ms_ >= 0 ? cntl->timeout_ms_ : options_.timeout_ms;
    if (timeout_ms > 0) {
        cntl->deadline_us_ = cntl->start_us_ + timeout_ms * 1000;
    }
    // Hop-to-hop deadline inheritance: a call issued inside a server
    // handler never outlives its upstream caller's patience — the
    // deadline is capped at the upstream remaining budget (which IssueRPC
    // then forwards downstream as the remaining-time meta), and the call
    // registers with the server call so an upstream cancel cascades into
    // it.
    Controller* parent = CurrentServerCall();
    if (parent != nullptr && parent->has_server_deadline()) {
        const int64_t upstream = parent->server_deadline_us();
        if (cntl->deadline_us_ == 0 || upstream < cntl->deadline_us_) {
            cntl->deadline_us_ = upstream;
        }
    }
    // QoS identity inheritance (ISSUE 8): a child call issued inside a
    // handler carries its upstream's tenant + priority unless the
    // handler set its own — the whole downstream tree of a low-priority
    // request stays sheddable, and a tenant's quota follows its traffic
    // through the mesh (same shape as the deadline cap above).
    if (parent != nullptr) {
        if (cntl->tenant().empty() && !parent->tenant().empty()) {
            cntl->set_tenant(parent->tenant());
        }
        if (!cntl->has_priority() && parent->has_priority()) {
            cntl->set_priority(parent->priority());
        }
        if (cntl->session().empty() && !parent->session().empty()) {
            cntl->set_session(parent->session());
        }
    }
    if (cntl->deadline_us_ > 0) {
        cntl->timeout_timer_ = TimerThread::singleton()->schedule(
            HandleTimeoutCb, (void*)(uintptr_t)cid, cntl->deadline_us_);
    }
    if (parent != nullptr && !parent->AddChildCall(cid)) {
        // The upstream call was canceled before this one even started:
        // queue the cancel on the locked id; it is delivered at unlock.
        id_error(cid, ECANCELED);
    }
    // Backup request timer (reference controller.cpp:344-358): fires
    // before the deadline, re-issues on a second call id, first response
    // wins. Requires retry budget (a backup consumes one retry). A
    // pluggable policy (retry_policy.h) decides the delay per call.
    const int64_t backup_ms =
        options_.backup_request_policy != nullptr
            ? options_.backup_request_policy->GetDelayMs(cntl)
            : (cntl->backup_request_ms_ >= 0 ? cntl->backup_request_ms_
                                             : options_.backup_request_ms);
    // Compare against the EFFECTIVE deadline (the inherited cap may be
    // tighter than the configured timeout): hedging past — or without —
    // remaining budget is pure waste, so a deadline that leaves less
    // than the hedge delay (including one already expired) suppresses
    // the timer; only a truly deadline-less call hedges unconditionally.
    const bool has_deadline = cntl->deadline_us_ > 0;
    const int64_t effective_timeout_ms =
        has_deadline ? (cntl->deadline_us_ - cntl->start_us_) / 1000 : 0;
    if (backup_ms >= 0 &&
        (!has_deadline || backup_ms < effective_timeout_ms)) {
        cntl->backup_timer_ = TimerThread::singleton()->schedule(
            &Controller::HandleBackupThunk, (void*)(uintptr_t)cid,
            cntl->start_us_ + backup_ms * 1000);
    }

    flight::Record(flight::kRpcIssue, cid, cntl->sampled_trace_id_);
    cntl->IssueRPC();
    id_unlock(cid);  // delivers any queued early error
    // `cntl` may already be gone here (async completion).

    if (done == nullptr) {
        // Synchronous call: wait for destroy (works from fibers and plain
        // pthreads alike — butex handles both waiter kinds).
        id_join(cid);
        // trpc.caller_wake: EndRPC's clock read (where trpc.match ends)
        // -> this caller running again. A synchronous caller owns `cntl`
        // until it returns, and every end passes through EndRPC.
        stage::Add(stage::kCallerWake,
                   stage::now_us() - (cntl->start_us_ + cntl->latency_us_));
    }
}

}  // namespace tpurpc
