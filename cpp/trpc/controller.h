// Controller: the per-RPC state machine and user knob surface, client and
// server side.
//
// Modeled on reference src/brpc/controller.h / controller.cpp: IssueRPC
// (:1047) picks the server + connection and writes the packed request;
// OnVersionedRPCReturned (:598) is the response/failure funnel handling
// retries via versioned call ids (:1059-1065) and timeouts (:593);
// Call::OnComplete (:780) feeds the load balancer. Implements
// google::protobuf::RpcController so generated stubs work unchanged.
#pragma once

#include <google/protobuf/message.h>
#include <google/protobuf/service.h>

#include <atomic>
#include <mutex>
#include <string>
#include <vector>

#include "tbase/endpoint.h"
#include "tbase/iobuf.h"
#include "tfiber/call_id.h"
#include "tfiber/timer_thread.h"
#include "tnet/socket.h"

namespace tpurpc {

namespace rpc {
class RpcMeta;
}

class Channel;
class Server;
namespace push_stream {
class StreamWriter;
}

class Controller : public google::protobuf::RpcController {
public:
    Controller() : excluded_(nullptr) { Reset(); }
    ~Controller() override;

    // ---- client-side knobs ----
    void set_timeout_ms(int64_t t) { timeout_ms_ = t; }
    int64_t timeout_ms() const { return timeout_ms_; }
    void set_max_retry(int r) { max_retry_ = r; }
    int max_retry() const { return max_retry_; }
    void set_log_id(int64_t id) { log_id_ = id; }
    int64_t log_id() const { return log_id_; }
    // Hash key for consistent-hashing load balancers (reference
    // Controller::set_request_code).
    void set_request_code(uint64_t code) {
        request_code_ = code;
        has_request_code_ = true;
    }
    // ---- multi-tenant QoS identity (ISSUE 8) ----
    // Client side: stamped into the request meta (tpu_std tenant/
    // priority fields; x-tpu-tenant/x-tpu-priority h2 headers). Server
    // side: parsed from the wire. Unset values inherit from the upstream
    // server call (Channel::CallMethod), so identity propagates
    // hop-to-hop alongside the deadline/trace context.
    void set_tenant(const std::string& t) { tenant_ = t; }
    const std::string& tenant() const { return tenant_; }
    // Priority class 0..7 (0 = most sheddable). Unset (-1) resolves to
    // the upstream call's class, else the middle class (qos.h
    // kDefaultPriority).
    void set_priority(int p) { priority_ = p; }
    int priority() const { return priority_; }
    bool has_priority() const { return priority_ >= 0; }
    // Sticky-session identity (ISSUE 16): names the client session this
    // call belongs to, so an L7 front door can pin the whole session to
    // one backend (rendezvous-hashed) and re-pin it atomically when that
    // backend drains. Rides the tpu_std request meta / the x-tpu-session
    // h2+HTTP header; propagates hop-to-hop like tenant/priority.
    void set_session(const std::string& s) { session_ = s; }
    const std::string& session() const { return session_; }
    // Server-suggested backoff attached to a TERR_OVERLOAD shed; on the
    // client it steers the retry delay (jittered), on the server the
    // response path copies it into the response meta.
    void set_suggested_backoff_ms(int64_t ms) { suggested_backoff_ms_ = ms; }
    int64_t suggested_backoff_ms() const { return suggested_backoff_ms_; }
    // Attachment bytes carried outside the pb payload (zero-copy).
    IOBuf& request_attachment() { return request_attachment_; }
    IOBuf& response_attachment() { return response_attachment_; }

    // ---- one-sided pool attachment (ISSUE 9) ----
    // Client: send `buf` as a (pool_id, offset, len, crc32c, epoch)
    // descriptor instead of inline frame bytes. Eligible when buf is one
    // contiguous block inside this process's SHARED registered pool (any
    // IOBuf block is, after IciBlockPool::Init, until it spills past the
    // primary region); ineligible bytes fall back to the inline
    // attachment transparently. The pin is held as a block LEASE
    // (tici/block_lease.h, ISSUE 10): the registry owns the block ref
    // until the RPC completes; EndRPC's release is exactly-once by
    // construction, the expiry reaper reclaims the pin if the call
    // wedges past its deadline, and peer death releases it through the
    // socket failure observer — the slab can never leak. Descriptors
    // only resolve on ici/shm links whose HANDSHAKE mapped our pool: the
    // receiver binds resolution to the connection's registered peer
    // pool (Socket::peer_pool_id), so a plain-TCP peer — or any
    // connection naming a pool that is not its own — answers
    // TERR_REQUEST; an epoch mismatch answers the retriable
    // TERR_STALE_EPOCH.
    void set_request_pool_attachment(IOBuf&& buf);
    bool has_request_pool_attachment() const {
        return pool_lease_id_ != 0;
    }
    // Lease handle of the pinned request attachment (0 = none/released);
    // tests assert exactly-once release through it.
    uint64_t pool_lease_id() const { return pool_lease_id_; }
    // Server: the resolved zero-copy view of a descriptor attachment —
    // bytes read IN PLACE from the receiver's mapping of the sender's
    // pool. Valid until the done closure runs; handlers must not retain
    // it past the response.
    struct PoolAttachment {
        const char* data = nullptr;
        uint64_t length = 0;
        uint64_t pool_id = 0;
        uint64_t offset = 0;
        uint32_t crc32c = 0;
        // Pool generation the descriptor was minted under (epoch fence).
        uint64_t pool_epoch = 0;
        // Response direction only: the completion token the view's
        // release echoes in its desc_ack (0 = token-less).
        uint64_t ack_token = 0;
    };
    const PoolAttachment& request_pool_attachment() const {
        return pool_attachment_;
    }
    bool has_request_pool_attachment_view() const {
        return pool_attachment_.data != nullptr;
    }
    // Server-protocol internal: install the resolved view.
    void SetRequestPoolAttachmentView(const PoolAttachment& view) {
        pool_attachment_ = view;
    }

    // ---- response-direction pool attachment (ISSUE 12) ----
    // Server handler side: answer with `buf` as a pool descriptor — the
    // symmetric twin of set_request_pool_attachment. Eligible when buf
    // is one contiguous block inside this process's shared pool AND the
    // call's connection rides a descriptor-capable transport tier
    // (tnet/transport.h — the client mapped our pool at handshake, or
    // is this process); anything else falls back to inline
    // response_attachment bytes transparently. The pin is a "rsp"
    // lease: the response closure arms it (owner = the wire correlation
    // id, expiry = the client's propagated deadline + grace, peer = the
    // server-side socket) and hands ownership to the registry — the
    // client's desc_ack releases it exactly once; the expiry reaper and
    // peer-death reclamation (a SIGKILLed client's socket failure)
    // are the crash-safe backstops.
    void set_response_pool_attachment(IOBuf&& buf);
    bool has_response_pool_attachment() const {
        return rsp_pool_lease_id_ != 0;
    }
    uint64_t response_pool_lease_id() const { return rsp_pool_lease_id_; }
    // Server-protocol internal: the stashed descriptor fields of the
    // pinned response attachment (valid while the lease lives).
    const PoolAttachment& response_pool_descriptor() const {
        return rsp_pool_stash_;
    }
    // Server-protocol internal: move the pin's ownership out of the
    // controller and into the wire/ack path (the response closure calls
    // this once it emits the descriptor; the controller's teardown then
    // no longer releases the pin — the ack/reaper/peer-death paths own
    // it). Returns 0 when there is nothing to take.
    uint64_t TakeResponsePoolLease() {
        const uint64_t id = rsp_pool_lease_id_;
        rsp_pool_lease_id_ = 0;
        return id;
    }
    // Client side: the resolved zero-copy view of a response descriptor
    // — bytes read IN PLACE from this process's mapping of the server's
    // pool. Valid until Reset()/destruction/reuse: releasing the view
    // sends the desc_ack that lets the server unpin the block, so user
    // code may read it after the call completes (sync callers included).
    // CAVEAT — the server's pin is deadline-bounded: its lease expires
    // at this call's propagated deadline + the server's
    // -pool_lease_grace_ms (or -pool_lease_default_ms for deadline-less
    // calls), after which the reaper may recycle the block even though
    // the view is still held. Consume the view promptly after the call
    // completes; a reader that dawdles past its own RPC deadline + the
    // grace window may observe recycled bytes (copy out early if you
    // must hold data longer).
    const PoolAttachment& response_pool_attachment() const {
        return rsp_pool_view_;
    }
    bool has_response_pool_attachment_view() const {
        return rsp_pool_view_.data != nullptr;
    }
    // Client-protocol internal: install the resolved view + the ack
    // identity (the socket the response arrived on and its wire
    // correlation id).
    void SetResponsePoolAttachmentView(const PoolAttachment& view,
                                       SocketId sid, uint64_t wire_cid) {
        rsp_pool_view_ = view;
        rsp_ack_sid_ = sid;
        rsp_ack_cid_ = wire_cid;
    }
    // Payload compression (reference set_request_compress_type /
    // set_response_compress_type; see trpc/compress.h). Attachments stay
    // raw. Client sets request_*; server handlers set response_*.
    void set_request_compress_type(int t) { request_compress_type_ = t; }
    int request_compress_type() const { return request_compress_type_; }
    void set_response_compress_type(int t) { response_compress_type_ = t; }
    int response_compress_type() const { return response_compress_type_; }

    // ---- results ----
    bool Failed() const override { return error_code_ != 0; }
    std::string ErrorText() const override { return error_text_; }
    int ErrorCode() const { return error_code_; }
    void SetFailed(const std::string& reason) override;
    void SetFailed(int error_code, const char* fmt, ...);
    int64_t latency_us() const { return latency_us_; }
    EndPoint remote_side() const { return remote_side_; }
    EndPoint local_side() const { return local_side_; }
    int retried_count() const { return current_try_; }
    // Hedge telemetry (ISSUE 16): whether a backup request actually went
    // out for this call, and whether the BACKUP try's response completed
    // the RPC (false when the original outran it, or the backup's
    // connection died and the call fell back to the original). An L7
    // router reads these after each forwarded call to account
    // rpc_router_hedges / rpc_router_hedge_wins without guessing from
    // global counters.
    bool backup_issued() const { return backup_issued_; }
    bool backup_won() const { return backup_won_; }
    // Combo-channel propagation hook: a SelectiveChannel sub-call runs
    // the backup machinery on its own sub-controller and mirrors the
    // telemetry onto the user-visible parent here.
    void set_backup_telemetry(bool issued, bool won) {
        backup_issued_ = issued;
        backup_won_ = won;
    }

    // The correlation id of this RPC (join it to wait for async calls).
    CallId call_id() const { return correlation_id_; }

    // Trace id of this call's rpcz span (0 = unsampled). Survives EndRPC
    // (the span itself is handed to the SpanDB) so a caller can chase the
    // call across the mesh at /rpcz/trace/<id>.
    uint64_t trace_id() const { return sampled_trace_id_; }

    // ---- protobuf::RpcController surface ----
    void Reset() override;
    void StartCancel() override;
    bool IsCanceled() const override {
        return canceled_.load(std::memory_order_acquire);
    }
    // Register `closure` to run when this call is canceled. Protobuf
    // contract: the closure runs EXACTLY once, whether or not
    // cancellation ever happens — an unfired closure runs at EndRPC /
    // Reset / destruction. Server side it may run on the connection's
    // input fiber, so it must be fast and must not block.
    void NotifyOnCancel(google::protobuf::Closure* closure) override;

    // ---- server side ----
    bool is_server_side() const { return server_ != nullptr; }
    Server* server() const { return server_; }
    // Called by the server-side protocol when building the call context.
    void InitServerSide(Server* server, const EndPoint& remote) {
        server_ = server;
        remote_side_ = remote;
    }
    // ---- server-side deadline (the client's propagated remaining
    // budget, parsed from tpu_std timeout_ms / h2 grpc-timeout) ----
    void set_server_deadline_us(int64_t d) { server_deadline_us_ = d; }
    bool has_server_deadline() const { return server_deadline_us_ > 0; }
    int64_t server_deadline_us() const { return server_deadline_us_; }
    // Remaining budget of this server call; INT64_MAX when the client
    // sent no deadline. May be <= 0 (already expired).
    int64_t remaining_server_budget_us() const;
    // ---- server-side cancellation (trpc/server_call.h registry) ----
    // The cancelable handle of this server call; its on_error handler is
    // HandleServerCancelThunk. Destroyed by the done closure.
    void set_server_call_id(CallId id) { server_call_id_ = id; }
    CallId server_call_id() const { return server_call_id_; }
    void DestroyServerCallId();
    // Mark this server call canceled: runs the NotifyOnCancel closure and
    // cascades ECANCELED into every downstream call the handler issued
    // under this context (stale-safe: completed children drop it).
    // Idempotent.
    void HandleServerCancel();
    static int HandleServerCancelThunk(CallId id, void* data, int error);

    // ---- streaming plumbing (see trpc/stream.h) ----
    // Client: StreamCreate records the local stream to announce in the
    // request meta; the response path connects or fails it.
    void set_request_stream(VRefId id, int64_t window) {
        request_stream_ = id;
        request_stream_window_ = window;
    }
    VRefId request_stream() const { return request_stream_; }
    int64_t request_stream_window() const { return request_stream_window_; }
    // Set once the response path bound the stream to a connection; EndRPC
    // fails any still-unbound stream so every termination path (timeout,
    // socket failure, server error, parse error) releases it (reference:
    // Controller::EndRPC -> HandleStreamConnection fails _request_stream).
    void set_request_stream_bound() { request_stream_bound_ = true; }
    // Server: the requester's announced stream (from request meta).
    void SetRemoteStream(uint64_t id, int64_t window) {
        remote_stream_id_ = id;
        remote_stream_window_ = window;
        has_remote_stream_ = true;
    }
    bool has_remote_stream() const { return has_remote_stream_; }
    uint64_t remote_stream_id() const { return remote_stream_id_; }
    int64_t remote_stream_window() const { return remote_stream_window_; }
    SocketId server_socket() const { return server_socket_; }
    void set_server_socket(SocketId sid) { server_socket_ = sid; }
    // Server: StreamAccept's local stream to announce in the response.
    void set_accepted_stream(VRefId id, int64_t window) {
        accepted_stream_ = id;
        accepted_stream_window_ = window;
    }
    VRefId accepted_stream() const { return accepted_stream_; }
    int64_t accepted_stream_window() const {
        return accepted_stream_window_;
    }

    // ---- server-push streams (ISSUE 17, push_stream tier) ----
    // Client: stamp a push-stream open/resume on the request meta
    // (StreamSettings{push=true, version, rx_window, resume_from_seq}).
    // StreamCall::PrepareOpen is the normal entry.
    void set_push_stream_request(uint64_t id, int64_t rx_window,
                                 uint64_t resume_from) {
        push_open_id_ = id;
        push_open_rx_window_ = rx_window;
        push_open_resume_from_ = resume_from;
    }
    // Server: the open parsed from the request meta (push=true).
    void SetPushStreamOpen(uint64_t id, int64_t rx_window,
                           uint64_t resume_from) {
        push_open_id_ = id;
        push_open_rx_window_ = rx_window;
        push_open_resume_from_ = resume_from;
        has_push_open_ = true;
    }
    bool has_push_stream_open() const { return has_push_open_; }
    uint64_t push_stream_id() const { return push_open_id_; }
    int64_t push_rx_window() const { return push_open_rx_window_; }
    uint64_t push_resume_from() const { return push_open_resume_from_; }
    // Accept the push open INSIDE the handler: registers (or resumes)
    // the server stream keyed by (session, stream_id) and returns the
    // writer. Chunks written before the response goes out queue in the
    // replay ring; the response closure binds the connection
    // (push_stream::Activate) and the writer starts/continues pushing.
    // Defined in stream.cc.
    push_stream::StreamWriter accept_stream();
    void set_accepted_push_stream(uint64_t id) {
        accepted_push_stream_ = id;
    }
    uint64_t accepted_push_stream() const { return accepted_push_stream_; }

private:
    friend class Channel;
    friend class Server;
    friend void ProcessTpuStdResponse(class TpuStdMessage* msg,
                                      const rpc::RpcMeta& meta);
    friend void CompleteClientUnaryResponse(uint64_t cid, int error_code,
                                            const std::string& error_text,
                                            IOBuf* payload_pb);

public:
    // Arm a backup request for this call at the given delay (overrides
    // ChannelOptions::backup_request_ms; <0 disables).
    void set_backup_request_ms(int64_t ms) { backup_request_ms_ = ms; }
    int64_t backup_request_ms() const { return backup_request_ms_; }

private:

    // Client call machinery (used by Channel).
    static int HandleErrorThunk(CallId id, void* data, int error);
    int HandleError(CallId id, int error);   // runs with the id locked
    void IssueRPC();                          // (re)send the current try
    void EndRPC(CallId locked_id);            // finalize: done/join wakeup
    static void* RunDoneThunk(void* arg);
    // Backup request machinery (reference controller.cpp:344-358,625-638
    // HandleBackupRequest): the timer fires at backup_request_ms; if the
    // RPC is still pending, a second call goes out on the next id version
    // while the original stays live — first response wins.
    static void HandleBackupThunk(void* arg);  // arg = base CallId value
    void MaybeIssueBackup();                   // runs with the id locked
    static void HandleBackoffThunk(void* arg);  // arg = retry's CallId
    // Report the finished try to the LB (latency + error feed the
    // locality-aware policy; reference Call::OnComplete controller.cpp:780).
    // `now_us`: the caller's clock read at this seam; 0 = read it here.
    void FeedbackToLB(int error, int64_t now_us = 0);
    // Pool-return / close this RPC's pooled/short connections (EndRPC).
    void ReleaseFlySockets();
    // Exactly-once release of the pinned pool-attachment lease (see
    // set_request_pool_attachment); safe on every termination path.
    void ReleasePoolLease();
    // Response-direction teardown, both roles: a server-side pin whose
    // ownership was never taken by the response closure (failed call,
    // non-tpu_std protocol) releases through the registry; a client-side
    // view sends the desc_ack that unpins the server's block. Runs on
    // Reset/reuse/destruction — never on EndRPC, so a sync caller can
    // still read the view after the call returns.
    void ReleaseResponsePoolState();
    // Best-effort wire CANCEL for the in-flight tries (tpu_std CANCEL
    // meta / h2 RST_STREAM) so the server stops burning CPU on a call
    // nobody waits for. Runs with the id locked.
    void SendWireCancel();
    // Run-once delivery of the NotifyOnCancel closure.
    void RunCancelClosure();
    // Downstream call registration for the cancellation cascade: returns
    // false when this (server-side) controller is already canceled — the
    // caller then cancels the fresh call instead of registering it.
    bool AddChildCall(CallId cid);

    // --- shared fields ---
    int error_code_;
    std::string error_text_;
    int64_t timeout_ms_;
    int max_retry_;
    int64_t log_id_;
    // Written by the cancel paths (client StartCancel; server: CANCEL
    // meta / RST_STREAM / connection death on the input fiber) and read
    // by the handler's fiber via IsCanceled().
    std::atomic<bool> canceled_{false};
    // NotifyOnCancel closure; exchanged to null on the (single) run.
    std::atomic<google::protobuf::Closure*> on_cancel_{nullptr};
    IOBuf request_attachment_;
    IOBuf response_attachment_;
    // One-sided descriptor state: the lease of the pinned pool block
    // (client; the block_lease registry owns the ref — EndRPC releases
    // it exactly once, the reaper/peer-death paths are the crash-safe
    // backstops) and the resolved in-place view (server).
    uint64_t pool_lease_id_ = 0;
    PoolAttachment pool_attachment_;
    // Response-direction descriptor state (ISSUE 12). Server role: the
    // "rsp" lease of the handler's pinned answer + its stashed
    // descriptor fields. Client role: the resolved in-place view and
    // the (socket, wire cid) identity its release acks.
    uint64_t rsp_pool_lease_id_ = 0;
    PoolAttachment rsp_pool_stash_;
    PoolAttachment rsp_pool_view_;
    SocketId rsp_ack_sid_ = INVALID_VREF_ID;
    uint64_t rsp_ack_cid_ = 0;
    EndPoint remote_side_;
    EndPoint local_side_;
    int64_t latency_us_;

    // --- client call state ---
    Channel* channel_;
    const google::protobuf::MethodDescriptor* method_;
    google::protobuf::Message* response_;
    google::protobuf::Closure* done_;
    CallId correlation_id_;   // base id (create version)
    CallId current_cid_;      // wire id of the current try
    // The still-live other in-flight call once a backup went out (the
    // reference's _unfinished_call): its response may win; its socket
    // errors kill only it.
    CallId unfinished_cid_;
    TimerId backup_timer_;
    int64_t backup_request_ms_;  // per-call override; <0 = channel default
    IOBuf request_buf_;       // serialized request payload (pb bytes)
    int current_try_;
    int64_t start_us_;
    int64_t deadline_us_;
    TimerId timeout_timer_;
    SocketId single_server_id_;
    SocketId current_server_id_;  // server of the in-flight try (LB mode)
    // Server of the still-live unfinished try once a backup went out:
    // FeedbackToLB(0) clears current_server_id_ when the backup issues,
    // so this keeps the loser's server addressable for the wire CANCEL
    // at EndRPC, and restores current_server_id_ when the backup's
    // connection dies and the call falls back to the original.
    SocketId unfinished_server_id_;
    bool backup_issued_;  // a backup try actually went out
    bool backup_won_;     // the backup try's response completed the RPC
    int64_t try_start_us_;        // start of the current try (LB feedback)
    int64_t reply_parsed_us_;     // stage clock: the winning reply parsed
    uint64_t request_code_;
    bool has_request_code_;
    int request_compress_type_;
    int response_compress_type_;
    // QoS identity (shared by both sides; see the accessors above).
    std::string tenant_;
    int priority_;  // -1 = unset
    std::string session_;  // sticky-session id (empty = none)
    int64_t suggested_backoff_ms_;
    // Pooled/short connection of the current try and of the still-live
    // original behind a backup (INVALID in single mode). A socket whose
    // call received a response is moved to reusable_fly_sid_ and returned
    // to the pool at EndRPC; anything else is closed (reference: a call
    // that fails without a response never reuses its pooled connection).
    SocketId current_fly_sid_;
    SocketId unfinished_fly_sid_;
    SocketId reusable_fly_sid_;
    // Socket whose auth fight THIS RPC's current try won (tpu_std);
    // aborted on retry/terminal failure so the connection can't wedge
    // with waiters parked behind a dead authenticator.
    SocketId auth_fight_sid_;
    class ExcludedServers* excluded_;  // servers tried by earlier attempts

    // --- streaming state ---
    VRefId request_stream_;
    int64_t request_stream_window_;
    bool request_stream_bound_;
    bool has_remote_stream_;
    uint64_t remote_stream_id_;
    int64_t remote_stream_window_;
    VRefId accepted_stream_;
    int64_t accepted_stream_window_;
    // push_stream tier (ISSUE 17): the open parsed from / stamped into
    // the request meta, and the stream id accepted by the handler.
    uint64_t push_open_id_;
    int64_t push_open_rx_window_;
    uint64_t push_open_resume_from_;
    bool has_push_open_;
    uint64_t accepted_push_stream_;
    SocketId server_socket_;

    // --- server call state ---
    Server* server_;
    // Absolute deadline propagated by the client (0 = none).
    int64_t server_deadline_us_ = 0;
    // Cancelable handle registered in server_call::Register.
    CallId server_call_id_ = INVALID_CALL_ID;
    // Downstream calls issued by the handler under this server context
    // (CallId VALUES only — cancellation via id_error is stale-safe, so
    // completed children need no deregistration).
    std::mutex child_mu_;
    std::vector<CallId> child_calls_;

public:
    // rpcz span of this RPC; null when unsampled. Client side: owned by
    // the controller from CallMethod until EndRPC submits it (all touches
    // run under the id lock). Server side: owned by the request pipeline
    // (request fiber -> user fiber -> done closure, strictly sequential).
    struct Span* span_ = nullptr;
    // The span's trace id, retained past span submission (trace_id()).
    uint64_t sampled_trace_id_ = 0;
};

// Generic client-side unary completion for protocols that frame outside
// tpu_std (h2/gRPC): locks `cid` (ranged, so backup winners work), moves
// the delivering pooled connection to reusable, records the error or
// parses `payload_pb` into the response message, and EndRPCs. Safe to
// call with a stale/finished cid (drops silently, like a late response).
void CompleteClientUnaryResponse(uint64_t cid, int error_code,
                                 const std::string& error_text,
                                 IOBuf* payload_pb);

// Shared client-side re-issue accounting (the single process-wide
// rpc_client_retries / rpc_retry_budget_exhausted adders live in
// controller.cc): combo channels route their own cross-channel retry
// loops through the same counters as the in-channel funnel.
namespace client_stats {
void CountRetry();            // rpc_client_retries
void CountBudgetExhausted();  // rpc_retry_budget_exhausted
}  // namespace client_stats

}  // namespace tpurpc
