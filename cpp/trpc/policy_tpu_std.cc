#include "trpc/policy_tpu_std.h"

#include <arpa/inet.h>
#include <csignal>

#include <cstring>
#include <mutex>

#include "rpc_meta.pb.h"
#include "tbase/errno.h"
#include "tbase/fast_rand.h"
#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "thttp/http2_client.h"
#include "thttp/http2_protocol.h"
#include "thttp/http_protocol.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tici/shm_link.h"
#include "tici/verbs.h"
#include "tnet/transport.h"
#include "tnet/fault_injection.h"
#include "tnet/input_messenger.h"
#include "trpc/auth.h"
#include "trpc/controller.h"
#include "tbase/crc32c.h"
#include "trpc/compress.h"
#include "trpc/pb_compat.h"
#include "trpc/redis.h"
#include "trpc/rpc_dump.h"
#include "trpc/server.h"
#include "trpc/server_call.h"
#include "trpc/span.h"
#include "trpc/stream.h"
#include "tvar/reducer.h"
#include "tvar/stage_recorder.h"

DECLARE_bool(rpc_checksum);

// Reference details/usercode_backup_pool.h: above this many in-flight
// user handlers, new ones run on an isolated worker pool (tag 63) so
// pthread-blocking user code cannot starve the IO path. <=0 disables.
DEFINE_int32(usercode_backup_threshold, 512,
             "in-flight user handlers before overflow is isolated");

// Push-stream descriptor eligibility (ISSUE 18 satellite): chunks at or
// above this ride descriptor-capable links as pool references instead
// of inline frame bytes; smaller chunks are not worth the pin+ack.
DEFINE_int64(stream_desc_min_bytes, 4096,
             "min push-stream chunk size sent as a pool descriptor on "
             "descriptor-capable links (first sends only; replays stay "
             "inline)");

namespace tpurpc {

namespace {
constexpr char kMagic[4] = {'T', 'R', 'P', 'C'};
constexpr size_t kHeaderLen = 12;
int g_tpu_std_index = -1;
}  // namespace

// Drain announcements received from peers (a GOAWAY meta marked this
// client's connection draining).
static LazyAdder g_drain_notices("rpc_client_drain_notices");
// One-sided descriptor resolution (ISSUE 9): attachments delivered as
// in-place views of a mapped sender pool — zero bytes copied.
static LazyAdder g_pool_desc_resolves("rpc_pool_descriptor_resolves");
static LazyAdder g_pool_desc_resolve_bytes(
    "rpc_pool_descriptor_resolve_bytes");
static LazyAdder g_pool_desc_rejects("rpc_pool_descriptor_rejects");
// Epoch-fence rejections (ISSUE 10b): descriptors minted under a pool
// generation this mapping no longer matches — answered with the
// retriable TERR_STALE_EPOCH, never a connection failure.
static LazyAdder g_pool_epoch_rejects("rpc_pool_epoch_rejects");
// Response-direction descriptor families (ISSUE 12): handlers answering
// with pool-block references — the symmetric twin of the request-side
// rpc_pool_descriptor_* counters.
static LazyAdder g_rsp_desc_sends("rpc_pool_desc_rsp_sends");
static LazyAdder g_rsp_desc_send_bytes("rpc_pool_desc_rsp_send_bytes");
static LazyAdder g_rsp_desc_fallbacks("rpc_pool_desc_rsp_fallbacks");
static LazyAdder g_rsp_desc_resolves("rpc_pool_desc_rsp_resolves");
static LazyAdder g_rsp_desc_resolve_bytes(
    "rpc_pool_desc_rsp_resolve_bytes");
static LazyAdder g_rsp_desc_rejects("rpc_pool_desc_rsp_rejects");
static LazyAdder g_rsp_desc_acks("rpc_pool_desc_rsp_acks");
// Push-stream chunks as descriptors (ISSUE 18 satellite): chunk sends
// that rode as pool references, shapes that fell back to inline bytes,
// receiver-side in-place resolves, and references the receiver could
// not honor (dropped frame — the stream's gap-NAK retransmit recovers
// the chunk inline).
static LazyAdder g_stream_desc_chunks("rpc_stream_desc_chunks");
static LazyAdder g_stream_desc_fallbacks("rpc_stream_desc_fallbacks");
static LazyAdder g_stream_desc_resolves("rpc_stream_desc_resolves");
static LazyAdder g_stream_desc_rejects("rpc_stream_desc_rejects");

namespace rsp_desc {
void CountSend(int64_t bytes) {
    *g_rsp_desc_sends << 1;
    *g_rsp_desc_send_bytes << bytes;
}
void CountFallback() { *g_rsp_desc_fallbacks << 1; }
void CountResolve(int64_t bytes) {
    *g_rsp_desc_resolves << 1;
    *g_rsp_desc_resolve_bytes << bytes;
}
void CountReject() { *g_rsp_desc_rejects << 1; }
void CountAck() { *g_rsp_desc_acks << 1; }
}  // namespace rsp_desc

int TpuStdProtocolIndex() { return g_tpu_std_index; }

ParseResult ParseTpuStdMessage(IOBuf* source, Socket* socket, bool read_eof,
                               const void* arg) {
    if (source->size() < kHeaderLen) {
        char head[4];
        const size_t n = source->copy_to(head, 4);
        if (memcmp(head, kMagic, n) != 0) {
            return ParseResult::make(ParseError::TRY_OTHERS);
        }
        return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
    }
    char aux[kHeaderLen];
    const char* header = (const char*)source->fetch(aux, kHeaderLen);
    if (memcmp(header, kMagic, 4) != 0) {
        return ParseResult::make(ParseError::TRY_OTHERS);
    }
    uint32_t body_size, meta_size;
    memcpy(&body_size, header + 4, 4);
    memcpy(&meta_size, header + 8, 4);
    body_size = ntohl(body_size);
    meta_size = ntohl(meta_size);
    if (meta_size > body_size || body_size > (256u << 20)) {
        return ParseResult::make(ParseError::ERROR);
    }
    if (source->size() < kHeaderLen + body_size) {
        return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
    }
    source->pop_front(kHeaderLen);
    auto* msg = new TpuStdMessage;
    source->cutn(&msg->meta, meta_size);
    source->cutn(&msg->body, body_size - meta_size);
    msg->byte_size = kHeaderLen + body_size;  // inline-dispatch size gate
    return ParseResult::make_ok(msg);
}

// Zero-cut fast path (ISSUE 7): classify the next frame of a sticky
// connection from the 12 contiguous header bytes — the messenger then
// waits for the announced frame size and calls parse exactly once, so a
// partially-arrived message costs no cutn and no re-parse per read.
int64_t PeekTpuStdFrame(const char* hdr, Socket*) {
    if (memcmp(hdr, kMagic, 4) != 0) return 0;  // re-sniff
    uint32_t body_size, meta_size;
    memcpy(&body_size, hdr + 4, 4);
    memcpy(&meta_size, hdr + 8, 4);
    body_size = ntohl(body_size);
    meta_size = ntohl(meta_size);
    if (meta_size > body_size || body_size > (256u << 20)) {
        return -1;  // corrupt: fail the connection
    }
    return (int64_t)kHeaderLen + body_size;
}

void SendTpuStdGoaway(Socket* s) {
    rpc::RpcMeta meta;
    meta.set_goaway(true);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
    s->Write(&frame);
}

void SendTpuStdCancel(SocketId sid, uint64_t cid) {
    rpc::RpcMeta meta;
    meta.set_correlation_id(cid);
    meta.set_cancel(true);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) == 0) {
        s->Write(&frame);
    }
}

void SendTpuStdDescAck(SocketId sid, uint64_t cid, uint64_t ack_token) {
    rpc::RpcMeta meta;
    meta.set_correlation_id(cid);
    meta.set_desc_ack(true);
    if (ack_token != 0) meta.set_desc_ack_token(ack_token);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) == 0) {
        s->Write(&frame);
    }
}

// ---- push-stream frames (ISSUE 17): meta-only frames with stream_frame
// set; DATA's chunk bytes ride as the frame payload.

int SendTpuStdStreamData(SocketId sid, uint64_t stream_id, uint64_t seq,
                         uint32_t flags, const std::string& chunk,
                         bool try_desc) {
    rpc::RpcMeta meta;
    auto* sf = meta.mutable_stream_frame();
    sf->set_stream_id(stream_id);
    sf->set_kind(1);  // KIND_DATA
    sf->set_seq(seq);
    if (flags != 0) sf->set_flags(flags);
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) != 0) return -1;
    // Descriptor-eligible chunk (ISSUE 18 satellite): pin a pool copy
    // and send the REFERENCE; the receiver resolves in place and
    // desc_acks with correlation id = seq (the lease's armed call id).
    // Every failure mode falls back to inline bytes — and a pin whose
    // frame never reaches the peer is freed by the lease reaper.
    IOBuf payload;
    bool desc_sent = false;
    if (try_desc && !chunk.empty() &&
        (int64_t)chunk.size() >= FLAGS_stream_desc_min_bytes.get() &&
        TransportDescriptorCapable(s.get())) {
        IOBuf pin;
        if (IciBlockPool::AllocatePoolAttachmentCopy(
                chunk.data(), chunk.size(), &pin)) {
            size_t blen = 0;
            const char* bdata = pin.backing_block_data(0, &blen);
            uint64_t off = 0;
            if (blen == chunk.size() &&
                IciBlockPool::OffsetOf(bdata, &off)) {
                const uint32_t crc = crc32c_extend(0, bdata, blen);
                const uint64_t lease =
                    block_lease::Pin(std::move(pin), "rsp");
                if (block_lease::Arm(lease, seq, 0, (uint64_t)sid)) {
                    auto* pd = sf->mutable_pool_attachment();
                    pd->set_pool_id(IciBlockPool::pool_id());
                    pd->set_offset(off);
                    pd->set_length(chunk.size());
                    pd->set_crc32c(crc);
                    pd->set_pool_epoch(IciBlockPool::pool_epoch());
                    pd->set_ack_token(lease);
                    desc_sent = true;
                    *g_stream_desc_chunks << 1;
                    transport_stats::AddDescOut(s->transport_tier(),
                                                (int64_t)chunk.size());
                } else {
                    block_lease::Release(lease);
                }
            }
        }
        if (!desc_sent) *g_stream_desc_fallbacks << 1;
    }
    if (!desc_sent) payload.append(chunk);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, payload, IOBuf());
    return s->Write(&frame);
}

int SendTpuStdStreamAck(SocketId sid, uint64_t stream_id, uint64_t ack_seq,
                        int64_t credits) {
    rpc::RpcMeta meta;
    auto* sf = meta.mutable_stream_frame();
    sf->set_stream_id(stream_id);
    sf->set_kind(2);  // KIND_ACK
    sf->set_ack_seq(ack_seq);
    if (credits != 0) sf->set_credits(credits);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) != 0) return -1;
    return s->Write(&frame);
}

int SendTpuStdStreamClose(SocketId sid, uint64_t stream_id,
                          int error_code) {
    rpc::RpcMeta meta;
    auto* sf = meta.mutable_stream_frame();
    sf->set_stream_id(stream_id);
    sf->set_kind(3);  // KIND_CLOSE
    if (error_code != 0) sf->set_error_code(error_code);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) != 0) return -1;
    return s->Write(&frame);
}

// ---- one-sided verbs (ISSUE 18): meta-only grant/verb frames and the
// hooks the pb-free tici/verbs layer calls through. WindowGrant frames
// correlate by correlation_id; verb frames correlate by wr_id.

namespace {

int SendVerbGrantRequest(uint64_t sid, uint64_t token, uint64_t length,
                         uint32_t mode, int64_t lease_ms) {
    rpc::RpcMeta meta;
    meta.set_correlation_id(token);
    auto* wg = meta.mutable_window_grant();
    wg->set_kind(1);  // REQUEST
    wg->set_length(length);
    wg->set_mode(mode);
    if (lease_ms > 0) wg->set_lease_ms(lease_ms);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
    SocketUniquePtr s;
    if (Socket::AddressSocket((SocketId)sid, &s) != 0) return -1;
    return s->Write(&frame);
}

// The wire emulation of one posted verb (verb-incapable tiers, and
// capable tiers whose mapping went stale): WRITE's gathered bytes ride
// as the frame body; READ is meta-only out, bytes come back on the
// completion frame.
int SendVerbWire(uint64_t sid, int op, uint64_t wr_id,
                 uint64_t window_id, uint64_t offset, uint64_t len,
                 uint64_t epoch, uint32_t crc, const IOBuf& payload) {
    rpc::RpcMeta meta;
    auto* vp = meta.mutable_verb_post();
    vp->set_op(op);
    vp->set_wr_id(wr_id);
    vp->set_window_id(window_id);
    vp->set_offset(offset);
    vp->set_length(len);
    vp->set_pool_epoch(epoch);
    if (crc != 0 || op == verbs::kRemoteWrite) vp->set_crc32c(crc);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, payload, IOBuf());
    SocketUniquePtr s;
    if (Socket::AddressSocket((SocketId)sid, &s) != 0) return -1;
    return s->Write(&frame);
}

bool VerbOneSidedProbe(uint64_t sid) {
    SocketUniquePtr s;
    if (Socket::AddressSocket((SocketId)sid, &s) != 0) return false;
    return TransportOneSided(s.get());
}

uint32_t VerbSglMaxProbe(uint64_t sid) {
    SocketUniquePtr s;
    if (Socket::AddressSocket((SocketId)sid, &s) != 0) return 0;
    return TransportSglMax(s.get());
}

}  // namespace

void PackTpuStdFrame(IOBuf* out, const IOBuf& meta_pb, const IOBuf& payload,
                     const IOBuf& attachment) {
    char header[kHeaderLen];
    memcpy(header, kMagic, 4);
    const uint32_t body =
        htonl((uint32_t)(meta_pb.size() + payload.size() + attachment.size()));
    const uint32_t meta = htonl((uint32_t)meta_pb.size());
    memcpy(header + 4, &body, 4);
    memcpy(header + 8, &meta, 4);
    out->append(header, kHeaderLen);
    out->append(meta_pb);
    out->append(payload);
    out->append(attachment);
}

// ---------------- server side ----------------

namespace {

// done-closure finishing one server call: serialize + respond + stats.
class SendResponseClosure : public google::protobuf::Closure {
public:
    SendResponseClosure(Server* server, Server::MethodCallGuard* guard,
                        Controller* cntl, google::protobuf::Message* req,
                        google::protobuf::Message* res, SocketId sid,
                        uint64_t cid)
        : server_(server),
          guard_(guard),
          cntl_(cntl),
          req_(req),
          res_(res),
          sid_(sid),
          cid_(cid) {}

    // Multi-tenant accounting (ISSUE 8): `counted` becomes true once the
    // request is admitted to service (direct dispatch or fair-queue
    // pop) — only then does Run() report completion to the QoS tier. A
    // queued item shed before service runs this closure with counted
    // still false: its shed was already counted at the eviction site,
    // and its latency must not pollute the tenant's served-p99 or teach
    // the cost model. `method`/`bytes`/`peer` feed the work-priced cost
    // model (ISSUE 15): measured service time + logical payload bytes
    // (inline + descriptor-exempt) fold into the estimate the NEXT
    // request of this (tenant, method) is charged.
    void set_qos(QosDispatcher* qos, QosDispatcher::TenantState* tenant,
                 int64_t start_us, const std::string& method,
                 int64_t logical_bytes, const EndPoint& peer) {
        qos_ = qos;
        qos_tenant_ = tenant;
        qos_start_us_ = start_us;
        qos_method_ = method;
        qos_bytes_ = logical_bytes;
        qos_peer_ = peer;
    }
    void set_qos_counted() { qos_counted_ = true; }
    uint64_t wire_cid() const { return cid_; }

    // Stage clock (tvar/stage_recorder.h). The call carries its last
    // stamp here; each seam reads the clock once, adds the stage that
    // ends there and moves the stamp on, so the five stages between the
    // request's first consumed byte and its reply's post (the socket's
    // writer adds the last, tnet.write_queue) telescope: their sum is
    // the call's residence in this process. `consumed_us` is the
    // messenger's stamp of the read that began the request, `parsed_us`
    // the clock read at the top of ProcessTpuStdRequest.
    void StartStages(int64_t consumed_us, int64_t parsed_us) {
        stage::Add(stage::kConsumeToCut,
                   consumed_us != 0 ? parsed_us - consumed_us : 0);
        stage_last_us_ = parsed_us;
    }
    // The handler is entered at `now_us` (inline round or its own fiber).
    void EnterHandler(int64_t now_us) {
        stage::Add(stage::kDispatchToHandler, now_us - stage_last_us_);
        stage_last_us_ = now_us;
        handler_entered_ = true;
        if (cntl_->span_ != nullptr) {
            cntl_->span_->process_start_us = now_us;
        }
    }

    void Run() override {
        flight::Record(flight::kRpcHandlerOut, cid_,
                       (uint64_t)cntl_->ErrorCode());
        const int64_t done_us = stage::now_us();
        // A call answered without its handler (shed, parse failure)
        // still passes every seam, with an empty handler stage.
        if (!handler_entered_) EnterHandler(done_us);
        stage::Add(stage::kHandler, done_us - stage_last_us_);
        stage_last_us_ = done_us;
        if (cntl_->span_ != nullptr) {
            cntl_->span_->process_end_us = done_us;
            // Annotated HERE, not in the cancel delivery path: the span is
            // owned by this strictly-sequential pipeline, and the cancel
            // thunk may race with span submission below.
            if (cntl_->IsCanceled()) {
                cntl_->span_->Annotate(
                    "canceled: upstream gave up (cascade delivered)");
            }
        }
        rpc::RpcMeta meta;
        auto* rmeta = meta.mutable_response();
        rmeta->set_error_code(cntl_->ErrorCode());
        if (cntl_->Failed()) {
            rmeta->set_error_text(cntl_->ErrorText());
            // Overload sheds tell the client when to come back; the
            // client jitters the value and spends a retry token.
            if (cntl_->ErrorCode() == TERR_OVERLOAD &&
                cntl_->suggested_backoff_ms() > 0) {
                rmeta->set_backoff_ms(cntl_->suggested_backoff_ms());
            }
        }
        meta.set_correlation_id(cid_);
        if (cntl_->accepted_stream() != INVALID_VREF_ID) {
            auto* ss = meta.mutable_stream_settings();
            ss->set_stream_id(cntl_->accepted_stream());
            ss->set_window_size(cntl_->accepted_stream_window());
        } else if (cntl_->accepted_push_stream() != 0) {
            // Push-stream accept echo (ISSUE 17): confirm the stream the
            // handler accepted; DATA starts flowing only after this
            // response is on the wire (Activate below).
            auto* ss = meta.mutable_stream_settings();
            ss->set_stream_id(cntl_->accepted_push_stream());
            ss->set_version(push_stream::kStreamVersion);
            ss->set_push(true);
        }
        IOBuf payload;
        if (!cntl_->Failed()) {
            if (!SerializePbToIOBuf(*res_, &payload)) {
                rmeta->set_error_code(TERR_RESPONSE);
                rmeta->set_error_text("serialize response failed");
                payload.clear();
            } else if (cntl_->response_compress_type() != COMPRESS_NONE) {
                IOBuf compressed;
                if (CompressBody(cntl_->response_compress_type(), payload,
                                 &compressed)) {
                    payload.swap(compressed);
                    meta.set_compress_type(cntl_->response_compress_type());
                }  // else: send uncompressed (compress_type stays unset)
            }
        }
        // Response-direction descriptor (ISSUE 12): the handler pinned a
        // pool block — arm its "rsp" lease with this call's identity
        // (owner = wire cid, expiry = the client's propagated deadline +
        // grace, peer = this connection) and emit the REFERENCE instead
        // of bytes. Ownership moves to the registry the moment the
        // descriptor goes on the wire: the client's desc_ack releases it
        // exactly once; a SIGKILLed client frees it through the socket
        // failure observer (server_call::OnSocketFailed -> ReleasePeer),
        // and the reaper covers a client that never acks.
        SocketUniquePtr s;
        const bool have_sock = Socket::AddressSocket(sid_, &s) == 0;
        if (cntl_->has_response_pool_attachment()) {
            const uint64_t rsp_lease = cntl_->TakeResponsePoolLease();
            const Controller::PoolAttachment& st =
                cntl_->response_pool_descriptor();
            const int64_t deadline = cntl_->has_server_deadline()
                                         ? cntl_->server_deadline_us()
                                         : 0;
            if (!cntl_->Failed() && have_sock &&
                block_lease::Arm(rsp_lease, cid_, deadline,
                                 (uint64_t)sid_)) {
                auto* pd = rmeta->mutable_pool_attachment();
                pd->set_pool_id(st.pool_id);
                pd->set_offset(st.offset);
                pd->set_length(st.length);
                pd->set_crc32c(st.crc32c);
                // Stamped at SEND time: a remap between the handler's
                // pin and this response carries the generation the
                // client's (re-)handshaken mapping expects.
                pd->set_pool_epoch(IciBlockPool::pool_epoch());
                // Completion token = the lease id: the ack releases by
                // direct lookup (call + connection still validated).
                pd->set_ack_token(rsp_lease);
                rsp_desc::CountSend((int64_t)st.length);
                transport_stats::AddDescOut(s->transport_tier(),
                                            (int64_t)st.length);
            } else {
                // Failed call, dead connection, or a pin the reaper
                // reclaimed under a wedged call: no reference may go
                // out. Drop the pin (exactly-once; a reaped lease is a
                // counted no-op) and — when the call would otherwise
                // report success — fail it with the retriable
                // stale-reference error instead of silently answering
                // without the attachment (data loss).
                block_lease::Release(rsp_lease);
                if (!cntl_->Failed()) {
                    rmeta->set_error_code(TERR_STALE_EPOCH);
                    rmeta->set_error_text(
                        "response pool pin reclaimed before send: "
                        "remap and retry");
                    payload.clear();
                }
            }
        }
        const IOBuf& att = cntl_->response_attachment();
        meta.set_attachment_size((uint32_t)att.size());
        if (FLAGS_rpc_checksum.get()) {
            uint32_t crc = crc32c_iobuf(0, payload);
            crc = crc32c_iobuf(crc, att);
            meta.set_body_checksum(crc);
        }
        IOBuf meta_buf;
        SerializePbToIOBuf(meta, &meta_buf);
        IOBuf frame;
        PackTpuStdFrame(&frame, meta_buf, payload, att);
        int wrc = -1;
        const int64_t enqueued_us = stage::now_us();
        stage::Add(stage::kRespond, enqueued_us - stage_last_us_);
        if (have_sock) {
            wrc = s->Write(&frame, 0, enqueued_us);
        }
        flight::Record(flight::kRpcWrite, cid_, payload.size());
        // Push-stream bind point (ISSUE 17): the accept echo is on the
        // wire — bind the stream to this connection, grant the open's
        // credit window and replay unacked ring entries. A failed call
        // or a dead connection aborts the open instead (without
        // unregistering an in-place resume's live generator: a fresh
        // resume re-open can still rescue it).
        if (cntl_->accepted_push_stream() != 0) {
            if (!cntl_->Failed() && wrc == 0) {
                push_stream::Activate(cntl_->accepted_push_stream(), sid_);
            } else {
                push_stream::AbortServerStream(
                    cntl_->accepted_push_stream(),
                    cntl_->Failed() ? cntl_->ErrorCode()
                                    : TERR_FAILED_SOCKET);
            }
        }
        if (cntl_->span_ != nullptr) {
            cntl_->span_->response_bytes = (int64_t)payload.size();
            cntl_->span_->end_us = enqueued_us;
            Collector::singleton()->submit(cntl_->span_);
            cntl_->span_ = nullptr;
        }
        // Cancellation teardown: deregister BEFORE destroying the id so
        // no new cancel can find a dying handle; DestroyServerCallId
        // serializes behind any in-flight cancel delivery (the thunk
        // holds the id lock while touching the controller).
        server_call::Unregister(sid_, cid_);
        cntl_->DestroyServerCallId();
        // Per-tenant completion BEFORE Finish: OnDone touches the
        // Server's QoS tier, and Finish must stay the LAST touch. The
        // completion info teaches the cost model and the tenant's
        // gradient limiter (failures punish the latency average).
        if (qos_tenant_ != nullptr && qos_counted_) {
            QosDispatcher::CompletionInfo ci;
            ci.error_code = cntl_->ErrorCode();
            ci.method = &qos_method_;
            ci.logical_bytes = qos_bytes_;
            ci.peer = qos_peer_;
            qos_->OnDone(qos_tenant_, enqueued_us - qos_start_us_, ci);
        }
        // Stats + limiter + Join wakeup; Finish is the LAST touch of
        // Server memory (the Server may be destroyed right after).
        guard_->Finish(cntl_->ErrorCode());
        delete guard_;
        delete req_;
        delete res_;
        delete cntl_;
        delete this;
    }

private:
    Server* server_;
    Server::MethodCallGuard* guard_;
    Controller* cntl_;
    google::protobuf::Message* req_;
    google::protobuf::Message* res_;
    SocketId sid_;
    uint64_t cid_;
    QosDispatcher* qos_ = nullptr;
    QosDispatcher::TenantState* qos_tenant_ = nullptr;
    int64_t qos_start_us_ = 0;
    bool qos_counted_ = false;
    int64_t stage_last_us_ = 0;    // the seam this call passed last
    bool handler_entered_ = false;
    std::string qos_method_;   // cost-model key ("Service.Method")
    int64_t qos_bytes_ = 0;    // inline + descriptor-exempt payload
    EndPoint qos_peer_;        // chaos cost_inflate scoping
};

// Carries one parsed request to its user-code fiber.
struct UserCallArgs {
    Server::MethodProperty* mp;
    Controller* cntl;
    google::protobuf::Message* req;
    google::protobuf::Message* res;
    google::protobuf::Closure* done;
    bool counted_default = false;  // holds a default-pool inflight count
};

// Usercode overload isolation (reference details/usercode_backup_pool.h
// TooManyUserCode): when too many user handlers occupy the DEFAULT pool
// — the hazard being handlers that BLOCK their worker pthread — the
// excess is routed to a reserved isolated tag pool so blocked user code
// can never consume every default worker and starve the IO fibers under
// it. Only default-pool residents are counted: once they drain below
// the threshold, new handlers use the default pool's free workers again
// instead of queueing behind the isolated backlog.
std::atomic<int64_t> g_usercode_default_inflight{0};
// kUsercodeBackupTag (policy_tpu_std.h): tag 63, reserved for this pool;
// Server::Start enforces the reservation.

// Last line of the expired-shed defense: the deadline may pass while the
// request waits for a handler fiber (queueing under overload is exactly
// when budgets die). True = the caller must run `done` WITHOUT invoking
// the service method.
bool ShedIfExpired(Server::MethodProperty* mp, Controller* cntl,
                   int64_t now_us) {
    if (!cntl->has_server_deadline() ||
        now_us < cntl->server_deadline_us()) {
        return false;
    }
    mp->status->nexpired.fetch_add(1, std::memory_order_relaxed);
    server_call::CountExpired();
    if (cntl->span_ != nullptr) {
        cntl->span_->Annotate(
            "deadline shed: expired before handler dispatch");
    }
    cntl->SetFailed(TERR_RPC_TIMEDOUT,
                    "deadline expired before handler dispatch");
    return true;
}

// Invoke the service method with the fiber-local server-call context
// published (Channel::CallMethod inside the handler inherits the
// remaining deadline and registers for the cancel cascade through it).
void CallUserMethod(Server::MethodProperty* mp, Controller* cntl,
                    google::protobuf::Message* req,
                    google::protobuf::Message* res,
                    google::protobuf::Closure* done) {
    // Within this protocol `done` is always the SendResponseClosure built
    // in ProcessTpuStdRequest -- the holder of the wire cid and of the
    // call's stage clock. One clock read for the handler-entry seam: the
    // stage, the rpcz phase and the deadline check share it.
    auto* closure = static_cast<SendResponseClosure*>(done);
    const int64_t entered_us = stage::now_us();
    closure->EnterHandler(entered_us);
    if (ShedIfExpired(mp, cntl, entered_us)) {
        done->Run();
        return;
    }
    // Grey-failure chaos seam (ISSUE 20): AFTER admission/shedding so
    // the fault degrades only what the server actually accepted —
    // health probes, QoS and the connection stay perfect; nothing but a
    // latency/error-observing client (the outlier tier) can tell.
    if (__builtin_expect(fault_injection_enabled(), 0)) {
        const FaultAction fa = FaultInjection::Decide(
            FaultOp::kHandler, cntl->remote_side(), 0);
        if (fa.kind == FaultAction::kFail) {
            // Synthetic post-admission failure WITHOUT running the
            // handler. TERR_OVERCROWDED: retriable (the soak must lose
            // zero completions — the client re-issues elsewhere) yet a
            // hard error to the breaker and the outlier detector
            // (unlike TERR_OVERLOAD, which admission control owns).
            cntl->SetFailed(TERR_OVERCROWDED,
                            "chaos: synthetic handler failure");
            done->Run();
            return;
        }
        if (fa.kind == FaultAction::kDelay) {
            // Service-time inflation: the node is SLOW, not dead.
            fiber_usleep(fa.delay_us);
        }
    }
    const uint64_t wire_cid = closure->wire_cid();
    flight::Record(flight::kRpcHandlerIn, wire_cid,
                   cntl->span_ != nullptr ? cntl->span_->trace_id : 0);
    ServerCallScope scope(cntl);
    mp->service->CallMethod(mp->method, cntl, req, res, done);
    // kRpcHandlerOut is recorded by SendResponseClosure::Run — a
    // synchronous handler has already run `done` (and freed cntl) here.
}

void* RunUserCall(void* arg) {
    auto* a = (UserCallArgs*)arg;
    const bool counted = a->counted_default;
    CallUserMethod(a->mp, a->cntl, a->req, a->res, a->done);
    delete a;
    if (counted) {
        g_usercode_default_inflight.fetch_sub(1, std::memory_order_relaxed);
    }
    return nullptr;
}

// Usercode overflow-isolation routing shared by the direct and queued
// dispatch paths: count default-pool residents, overflow past the
// threshold onto the reserved backup tag.
FiberAttr UserCallAttr(Server* server, UserCallArgs* uc) {
    FiberAttr attr = FIBER_ATTR_NORMAL;
    attr.tag = server->options().fiber_tag;
    const int32_t backup_at = FLAGS_usercode_backup_threshold.get();
    if (attr.tag == 0 && backup_at > 0) {
        const int64_t inflight = g_usercode_default_inflight.fetch_add(
                                     1, std::memory_order_relaxed) +
                                 1;
        if (inflight > backup_at) {
            g_usercode_default_inflight.fetch_sub(
                1, std::memory_order_relaxed);
            attr.tag = kUsercodeBackupTag;  // overflow: isolated pool
        } else {
            uc->counted_default = true;
        }
    }
    return attr;
}

// `fail_after`: the socket fails with that error once this reply is
// posted (Socket::Write).
void SendErrorResponse(SocketId sid, uint64_t cid, int err,
                       const std::string& text, int64_t backoff_ms = 0,
                       int fail_after = 0) {
    rpc::RpcMeta meta;
    meta.mutable_response()->set_error_code(err);
    meta.mutable_response()->set_error_text(text);
    if (backoff_ms > 0) {
        meta.mutable_response()->set_backoff_ms(backoff_ms);
    }
    meta.set_correlation_id(cid);
    IOBuf meta_buf;
    SerializePbToIOBuf(meta, &meta_buf);
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) == 0) {
        if (s->Write(&frame, 0, 0, fail_after) != 0 && fail_after != 0) {
            s->SetFailedWithError(fail_after);  // no reply could be queued
        }
    }
}

// ---- fair-queue dispatch units (ISSUE 8) ----
// A request parked in the weighted-fair queue, ready for either service
// (drainer pop -> background handler fiber) or a priority shed.
struct QueuedCall {
    Server* server;
    Server::MethodProperty* mp;
    Controller* cntl;
    google::protobuf::Message* req;
    google::protobuf::Message* res;
    SendResponseClosure* done;
};

void RunQueuedCall(void* arg) {
    auto* qd = (QueuedCall*)arg;
    // Popped = admitted (the dispatcher accounted it): completions now
    // report to the QoS tier.
    qd->done->set_qos_counted();
    auto* uc = new UserCallArgs{qd->mp, qd->cntl, qd->req, qd->res,
                                qd->done};
    FiberAttr attr = UserCallAttr(qd->server, uc);
    fiber_t tid;
    // Always BACKGROUND from the drainer: an urgent handoff would park
    // the drainer fiber behind this handler and serialize the queue.
    if (fiber_start_background(&tid, &attr, RunUserCall, uc) != 0) {
        const bool counted = uc->counted_default;
        delete uc;
        if (counted) {
            g_usercode_default_inflight.fetch_sub(
                1, std::memory_order_relaxed);
        }
        // Fiber system saturated/shutting down — the overload case
        // itself. Running the handler INLINE here would head-of-line-
        // block the single drainer fiber and stall every queued tenant
        // (the opposite of the isolation guarantee): shed instead. The
        // closure still settles accounting (it was counted at pop).
        qd->cntl->set_suggested_backoff_ms(
            qd->server->qos()->SuggestedBackoffMs());
        qd->cntl->SetFailed(TERR_OVERLOAD,
                            "no worker fiber available for dispatch");
        qd->done->Run();
    }
    delete qd;
}

void ShedQueuedCall(void* arg, int64_t backoff_ms) {
    auto* qd = (QueuedCall*)arg;
    // The closure answers TERR_OVERLOAD (+ suggested backoff in the
    // response meta) and settles admission/stats/cancel-registry — the
    // same single funnel a served request uses.
    qd->cntl->set_suggested_backoff_ms(backoff_ms);
    qd->cntl->SetFailed(TERR_OVERLOAD,
                        "shed under overload: evicted from the fair "
                        "queue (lowest priority first)");
    if (qd->cntl->span_ != nullptr) {
        qd->cntl->span_->Annotate("overload shed: evicted from fair queue");
    }
    qd->done->Run();
    delete qd;
}

void ProcessTpuStdRequest(TpuStdMessage* msg, const rpc::RpcMeta& meta) {
    const SocketId sid = msg->socket_id;
    const uint64_t cid = meta.correlation_id();
    // The message is cut and its meta parsed: the one clock read of this
    // seam is the request's arrival for its deadline, the QoS bucket and
    // the rpcz span, and ends tnet.consume_to_cut (StartStages below).
    const int64_t arrival_us = stage::now_us();
    flight::Record(flight::kRpcDispatch, cid, msg->body.size());
    // rpc_dump: capture the raw meta+body of sampled requests (reference
    // rpc_dump.cpp via the bvar Collector; appending IOBufs only bumps
    // block refcounts, so the hot path pays two flag/gate loads).
    if (IsRpcDumpSampled()) {
        SubmitRpcDump(msg->meta, msg->body);
    }
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) != 0) return;
    InputMessenger* m = (InputMessenger*)s->user();
    Server* server = m != nullptr ? (Server*)m->context : nullptr;
    if (server == nullptr) {
        return;  // no server bound (shutting down)
    }
    // Connection-level authentication (the Protocol `verify` hook,
    // reference protocol.h:77-172): the FIRST request must carry a valid
    // credential; the connection is trusted afterwards. Bad credentials
    // fail the whole connection, not just the call.
    if (server->options().auth != nullptr && !s->authenticated()) {
        AuthContext actx;
        if (!meta.has_auth_data() ||
            server->options().auth->VerifyCredential(
                meta.auth_data(), s->remote_side(), &actx) != 0) {
            // The caller reads TERR_AUTH, then the connection goes.
            SendErrorResponse(sid, cid, TERR_AUTH, "authentication failed",
                              0, TERR_AUTH);
            return;
        }
        s->SetAuthenticated(actx.user());
    }
    const auto& req_meta = meta.request();
    Server::MethodProperty* mp =
        server->FindMethod(req_meta.service_name(), req_meta.method_name());
    if (mp == nullptr) {
        SendErrorResponse(sid, cid, TERR_NO_METHOD,
                          "no such method " + req_meta.service_name() + "." +
                              req_meta.method_name());
        return;
    }
    // Server-side deadline: the meta carries the client's REMAINING
    // budget at send time (IssueRPC stamps (deadline - now)/1000, so a
    // caller that has already given up stamps <= 0). Shed expired
    // requests here — before admission, before parse, before a handler
    // fiber — executing them is pure waste the client will never read.
    int64_t deadline_us = 0;
    if (req_meta.has_timeout_ms()) {
        if (req_meta.timeout_ms() <= 0) {
            mp->status->nexpired.fetch_add(1, std::memory_order_relaxed);
            server_call::CountExpired();
            SendErrorResponse(sid, cid, TERR_RPC_TIMEDOUT,
                              "deadline already expired on arrival");
            return;
        }
        deadline_us = arrival_us + req_meta.timeout_ms() * 1000;
    }
    // Multi-tenant QoS stage 1 (ISSUE 8 + 15): identity + WORK-PRICED
    // rate quota. The tenant's token bucket answers BEFORE admission,
    // parse, or any allocation — charged this (tenant, method)'s
    // measured cost estimate, not a flat request count, so a tenant
    // inside its request rate cannot sink the server with
    // few-but-heavy calls. Cross-zone spill arrivals pay the
    // -rpc_spill_cost_multiplier on top. A flooding tenant is shed at
    // the cost of one bucket CAS, with TERR_OVERLOAD and a computed
    // "come back in N ms" that the client jitters (deadline-capped)
    // while spending retry budget.
    QosDispatcher* qos = server->qos();
    const bool qos_on = qos->enabled();
    QosDispatcher::TenantState* tstate = nullptr;
    const int priority = ClampPriority(
        req_meta.has_priority() ? req_meta.priority() : kDefaultPriority);
    const std::string method_key =
        req_meta.service_name() + "." + req_meta.method_name();
    int64_t cost_milli = kCostUnitMilli;
    bool spill = false;
    if (qos_on) {
        tstate = qos->Acquire(req_meta.tenant());
        cost_milli = qos->EstimateCostMilli(tstate, method_key);
        if (req_meta.has_zone() && SpillArrival(req_meta.zone())) {
            spill = true;
            cost_milli = SpillAdjustedCostMilli(cost_milli);
        }
        int64_t backoff_ms = 0;
        if (!qos->AdmitCost(tstate, arrival_us, cost_milli, &backoff_ms)) {
            SendErrorResponse(sid, cid, TERR_OVERLOAD,
                              "tenant '" + tstate->name +
                                  "' over its cost quota",
                              backoff_ms);
            return;
        }
    }
    // Admission control (reference ConcurrencyLimiter::OnRequested —
    // constant or gradient "auto" per ServerOptions). The remaining
    // budget rides along so the timeout limiter can shed requests that
    // cannot finish in time (AdmitWithBudget probes per priority class).
    auto* guard = new Server::MethodCallGuard(
        server, mp, deadline_us > 0 ? deadline_us - arrival_us : -1,
        priority);
    if (guard->rejected() && !guard->shed() && qos_on &&
        qos->EvictOneBelow(priority)) {
        // Priority-aware relief: a lower-priority queued request was
        // evicted (answered TERR_OVERLOAD); this request takes its place
        // with the concurrency check waived — net concurrency unchanged,
        // lowest priority shed first instead of first-come-first-served.
        delete guard;
        guard = new Server::MethodCallGuard(
            server, mp, deadline_us > 0 ? deadline_us - arrival_us : -1,
            priority, /*forced=*/true);
    }
    if (guard->rejected()) {
        const bool shed = guard->shed();
        delete guard;
        if (shed) {
            server_call::CountShed();
            SendErrorResponse(sid, cid, TERR_LIMIT_EXCEEDED,
                              "remaining deadline budget below observed "
                              "service time");
        } else if (qos_on) {
            // Overload, and nothing below this priority to evict: shed
            // with the retriable-with-backoff error so well-behaved
            // clients spread their re-issues.
            qos->CountShed(tstate, cost_milli);
            SendErrorResponse(sid, cid, TERR_OVERLOAD,
                              "overloaded: concurrency limit, no lower-"
                              "priority work to shed",
                              qos->SuggestedBackoffMs());
        } else {
            SendErrorResponse(sid, cid, TERR_LIMIT_EXCEEDED,
                              "concurrency limit");
        }
        return;
    }

    // Split payload / attachment.
    const uint32_t att_size = meta.attachment_size();
    if ((size_t)att_size > msg->body.size()) {
        guard->Finish(TERR_REQUEST);
        delete guard;
        SendErrorResponse(sid, cid, TERR_REQUEST,
                          "attachment_size exceeds body");
        return;
    }
    if (meta.has_body_checksum() &&
        crc32c_iobuf(0, msg->body) != meta.body_checksum()) {
        guard->Finish(TERR_REQUEST);
        delete guard;
        SendErrorResponse(sid, cid, TERR_REQUEST, "body checksum mismatch");
        return;
    }
    IOBuf payload;
    IOBuf attachment;
    const size_t payload_size = msg->body.size() - att_size;
    msg->body.cutn(&payload, payload_size);
    attachment.swap(msg->body);
    if (meta.compress_type() != COMPRESS_NONE) {
        IOBuf raw;
        if (!DecompressBody(meta.compress_type(), payload, &raw)) {
            guard->Finish(TERR_REQUEST);
            delete guard;
            SendErrorResponse(sid, cid, TERR_REQUEST,
                              "decompress request failed");
            return;
        }
        payload.swap(raw);
    }
    // One-sided pool attachment (ISSUE 9b): the meta names (pool_id,
    // offset, len, crc) in the SENDER's registered pool; resolve it
    // against our mapping of that pool (registered at the ICI
    // handshake) and hand the handler an in-place view — the payload
    // bytes are never copied host-side. Unknown pool = the sender used
    // descriptors on a link whose handshake never mapped its pool
    // (plain TCP): fail the call, not the connection.
    Controller::PoolAttachment pool_view;
    if (meta.has_pool_attachment()) {
        const auto& pd = meta.pool_attachment();
        // Scope check BEFORE the registry — now the Transport seam's
        // verdict (ISSUE 12): a connection may only reference the pool
        // its OWN handshake mapped (or, on an in-process transport
        // link, this process's pool), and only on a descriptor-capable
        // tier. The global registry alone must never authorize — any
        // connection could otherwise name another tenant's mapped pool,
        // or a plain-TCP peer this server's own, and read memory it was
        // never handed.
        const bool in_scope =
            TransportDescriptorScopeOk(s.get(), pd.pool_id());
        const char* pool_base = nullptr;
        size_t pool_size = 0;
        uint64_t map_epoch = 0;
        if (!in_scope ||
            !pool_registry::Resolve(pd.pool_id(), &pool_base,
                                    &pool_size, &map_epoch) ||
            pd.offset() > pool_size ||
            pd.length() > pool_size - pd.offset()) {
            *g_pool_desc_rejects << 1;
            guard->Finish(TERR_REQUEST);
            delete guard;
            SendErrorResponse(sid, cid, TERR_REQUEST,
                              "unresolvable pool descriptor (sender pool "
                              "not mapped on this link, or out of "
                              "bounds)");
            return;
        }
        // Chaos seam (chaos_pool, ISSUE 10d): crc corruption and stale-
        // epoch injection on the resolve path — both must fail ONLY
        // this call while the connection (and every other in-flight
        // descriptor) keeps working.
        bool chaos_corrupt = false;
        bool chaos_stale = false;
        if (__builtin_expect(fault_injection_enabled(), 0)) {
            const FaultAction fault = FaultInjection::Decide(
                FaultOp::kPoolResolve, s->remote_side(), pd.length());
            chaos_corrupt = fault.kind == FaultAction::kCorrupt;
            chaos_stale = fault.kind == FaultAction::kStaleEpoch;
        }
        // Epoch fence BEFORE the crc read: a descriptor minted under an
        // older (or injected-stale) generation may point at recycled
        // bytes — reject it as the RETRIABLE stale-reference error
        // without touching the memory. Absent/0 epoch = pre-epoch
        // sender, fence skipped (mixed-version caveat).
        if ((pd.has_pool_epoch() && pd.pool_epoch() != 0 &&
             pd.pool_epoch() != map_epoch) ||
            chaos_stale) {
            *g_pool_epoch_rejects << 1;
            guard->Finish(TERR_STALE_EPOCH);
            delete guard;
            SendErrorResponse(sid, cid, TERR_STALE_EPOCH,
                              "stale pool descriptor epoch (mapping at " +
                                  std::to_string(map_epoch) +
                                  "): remap and retry");
            return;
        }
        if ((pd.has_crc32c() &&
             crc32c_extend(0, pool_base + pd.offset(), pd.length()) !=
                 pd.crc32c()) ||
            chaos_corrupt) {
            *g_pool_desc_rejects << 1;
            guard->Finish(TERR_REQUEST);
            delete guard;
            SendErrorResponse(sid, cid, TERR_REQUEST,
                              "pool descriptor crc32c mismatch");
            return;
        }
        pool_view.data = pool_base + pd.offset();
        pool_view.length = pd.length();
        pool_view.pool_id = pd.pool_id();
        pool_view.offset = pd.offset();
        pool_view.crc32c = pd.crc32c();
        pool_view.pool_epoch = pd.pool_epoch();
        *g_pool_desc_resolves << 1;
        *g_pool_desc_resolve_bytes << (int64_t)pd.length();
        // The logical payload is exempt from the inline-dispatch byte
        // budget (only the tiny wire frame was charged — the referenced
        // bytes never pass through the message path), and it IS this
        // connection's data-plane throughput: attribute it.
        if (inline_dispatch::RoundArmed()) {
            inline_dispatch::ExemptDescriptorBytes(pd.length());
        }
        s->add_descriptor_bytes_read((int64_t)pd.length());
        transport_stats::AddDescIn(s->transport_tier(),
                                   (int64_t)pd.length());
    }

    auto* req = mp->service->GetRequestPrototype(mp->method).New();
    auto* res = mp->service->GetResponsePrototype(mp->method).New();
    auto* cntl = new Controller;
    cntl->InitServerSide(server, s->remote_side());
    cntl->set_server_socket(sid);
    cntl->set_server_deadline_us(deadline_us);
    // Expose the request's compression to the handler (reference
    // Controller::request_compress_type); the response defaults to none
    // unless the handler opts in.
    cntl->set_request_compress_type(meta.compress_type());
    // QoS identity on the call context: handler-issued child calls
    // inherit it (Channel::CallMethod), so a tenant's class follows its
    // traffic through the mesh.
    if (req_meta.has_tenant()) cntl->set_tenant(req_meta.tenant());
    cntl->set_priority(priority);
    if (req_meta.has_session()) cntl->set_session(req_meta.session());
    // Interceptor (reference interceptor.h:30 Interceptor::Accept runs
    // before the service method; rejection answers the error directly).
    if (server->options().interceptor != nullptr) {
        int err = 0;
        std::string etext;
        if (!server->options().interceptor->Accept(cntl, &err, &etext)) {
            guard->Finish(err != 0 ? err : TERR_REQUEST);
            delete guard;
            delete cntl;
            delete req;
            delete res;
            SendErrorResponse(sid, cid, err != 0 ? err : TERR_REQUEST,
                              etext.empty() ? "rejected by interceptor"
                                            : etext);
            return;
        }
    }
    // rpcz: with rpcz locally enabled, an upstream-sampled trace is
    // always continued (skipping the rate gate); otherwise the local gate
    // may start one. A disabled server NEVER allocates spans — peers must
    // not control that cost (reference span.h:236-240 enable_rpcz).
    if (IsRpczEnabled() && (req_meta.has_trace_id() || IsRpczSampled())) {
        auto* span = new Span;
        span->kind = Span::SERVER;
        span->trace_id =
            req_meta.has_trace_id() ? req_meta.trace_id() : fast_rand();
        span->parent_span_id =
            req_meta.has_span_id() ? req_meta.span_id() : 0;
        span->span_id = fast_rand();
        span->method =
            req_meta.service_name() + "." + req_meta.method_name();
        span->remote_side = s->remote_side();
        span->start_us = arrival_us;
        span->request_bytes = (int64_t)payload_size + att_size;
        cntl->span_ = span;
    }
    if (meta.has_stream_settings()) {
        const auto& ss = meta.stream_settings();
        if (ss.push()) {
            // Push-stream open/resume (ISSUE 17). A version newer than
            // ours is rejected below (fails the CALL — retriable at the
            // caller — never the connection).
            if (ss.version() <= push_stream::kStreamVersion) {
                cntl->SetPushStreamOpen(ss.stream_id(), ss.rx_window(),
                                        ss.resume_from_seq());
            }
        } else {
            cntl->SetRemoteStream(ss.stream_id(), ss.window_size());
        }
    }
    cntl->request_attachment() = attachment;
    if (pool_view.data != nullptr) {
        cntl->SetRequestPoolAttachmentView(pool_view);
    }
    // Cancelable handle: a tpu_std CANCEL meta, an h2 RST, or this
    // connection's death reaches the controller through the registry
    // (trpc/server_call.h); the done closure tears both down. Every path
    // from here runs the done closure, so the registration cannot leak.
    CallId scid = INVALID_CALL_ID;
    if (id_create(&scid, cntl, &Controller::HandleServerCancelThunk) == 0) {
        cntl->set_server_call_id(scid);
        server_call::Register(sid, cid, scid);
    }
    auto* done = new SendResponseClosure(server, guard, cntl, req, res, sid,
                                         cid);
    done->StartStages(msg->consumed_us, arrival_us);
    if (qos_on) {
        // Logical payload = inline body + attachment + the descriptor-
        // exempt referenced bytes (they never rode the message path but
        // they ARE the work this request represents).
        const int64_t logical_bytes =
            (int64_t)payload_size + (int64_t)att_size +
            (pool_view.data != nullptr ? (int64_t)pool_view.length : 0);
        done->set_qos(qos, tstate, arrival_us, method_key, logical_bytes,
                      s->remote_side());
    }
    if (!ParsePbFromIOBuf(req, payload)) {
        cntl->SetFailed(TERR_REQUEST, "parse request failed");
        done->Run();
        return;
    }
    if (meta.has_stream_settings() && meta.stream_settings().push() &&
        meta.stream_settings().version() > push_stream::kStreamVersion) {
        // Version-skewed push open: answer the call with a clean error
        // (the handler never runs, the connection stays healthy).
        cntl->SetFailed(TERR_REQUEST,
                        "unsupported push-stream version");
        done->Run();
        return;
    }
    // Multi-tenant QoS stage 3 (ISSUE 8): the weighted-fair dispatch
    // queue sits in front of handler spawn. Uncontended (queue empty,
    // tenant under its concurrency share) the request dispatches
    // DIRECTLY below — the PR-6 inline fast path stays legal exactly
    // then, so fairness never regresses the raw-speed win on
    // uncontended sockets. Contended, the request parks under
    // (priority, tenant-DRR) and the drainer fiber spawns handlers in
    // fair order; past the high-water the lowest-priority queued
    // request is shed first.
    if (qos_on) {
        if (!qos->TryDirectDispatch(tstate, cost_milli)) {
            auto* qd = new QueuedCall{server, mp, cntl, req, res, done};
            QosDispatcher::Item item;
            item.run = RunQueuedCall;
            item.shed = ShedQueuedCall;
            item.arg = qd;
            // The queued item carries its estimated (spill-adjusted)
            // charge: the DRR dequeue burns it against the tenant's
            // deficit, and spill items shed first within their level.
            item.cost_milli = cost_milli;
            item.spill = spill;
            qos->Enqueue(tstate, priority, item);
            return;
        }
        done->set_qos_counted();
    }
    // User code normally runs on its OWN fiber, never this one: a slow
    // handler on the input fiber would head-of-line-block the connection —
    // the backup request riding the same socket would not even be PARSED
    // until the original finished (reference keeps user code off the input
    // path: baidu_rpc_protocol.cpp:758,839-849,
    // details/usercode_backup_pool.h).
    //
    // Run-to-completion exception (ISSUE 7): a method flagged inline-safe
    // (Server::SetMethodInlineSafe — its handler promises to be cheap and
    // to NEVER block) runs right here. On the input fiber that means
    // read -> parse -> handler -> response write in one go, with the
    // response joining the round's coalesced writev.
    const bool method_inline =
        mp->inline_safe.load(std::memory_order_relaxed);
    if (server->options().usercode_inline || method_inline) {
        if (method_inline) inline_dispatch::CountHandlerInline();
        CallUserMethod(mp, cntl, req, res, done);
        return;
    }
    auto* uc = new UserCallArgs{mp, cntl, req, res, done};
    fiber_t tid;
    FiberAttr attr = UserCallAttr(server, uc);
    // Mid-burst (running on the input fiber with MORE bytes already read
    // and waiting in the cut loop): spawn in the BACKGROUND — an urgent
    // handoff would park the input fiber and serialize the whole burst
    // behind this handler. Give the budget unit back; this message fanned
    // out after all. The last/solo message of a wake (read_buf drained —
    // the classic single-request case) keeps the urgent path: the handler
    // takes this worker NOW, the input fiber has at most a read-EAGAIN
    // left (the reference's run-bthread-immediately ProcessEvent/usercode
    // spawns). read_buf is input-fiber-owned, and RoundArmed() is only
    // true ON the input fiber, so the read is race-free.
    const bool mid_burst =
        inline_dispatch::RoundArmed() && !s->read_buf.empty();
    if (mid_burst) inline_dispatch::Refund();
    const int spawn_rc =
        mid_burst ? fiber_start_background(&tid, &attr, RunUserCall, uc)
                  : fiber_start_urgent(&tid, &attr, RunUserCall, uc);
    if (spawn_rc != 0) {
        const bool counted = uc->counted_default;
        delete uc;  // fall back inline (fiber system saturated/shut down)
        if (counted) {
            g_usercode_default_inflight.fetch_sub(
                1, std::memory_order_relaxed);
        }
        CallUserMethod(mp, cntl, req, res, done);
    }
}

}  // namespace

// ---------------- client side ----------------

void ProcessTpuStdResponse(TpuStdMessage* msg, const rpc::RpcMeta& meta);

void ProcessTpuStdMessage(InputMessageBase* raw) {
    std::unique_ptr<TpuStdMessage> msg((TpuStdMessage*)raw);
    rpc::RpcMeta meta;
    if (!ParsePbFromIOBuf(&meta, msg->meta)) {
        SocketUniquePtr s;
        if (Socket::AddressSocket(msg->socket_id, &s) == 0) {
            s->SetFailedWithError(TERR_REQUEST);
        }
        return;
    }
    if (meta.goaway()) {
        // Drain announcement (the tpu_std GOAWAY): the peer is shutting
        // down deliberately. Mark the connection draining — in-flight
        // calls on it complete normally (the server keeps serving through
        // its drain window); NEW calls steer away (LB skips draining
        // nodes, pinned channels re-create).
        SocketUniquePtr s;
        if (Socket::AddressSocket(msg->socket_id, &s) == 0 &&
            !s->Draining()) {
            s->SetDraining();
            *g_drain_notices << 1;
        }
        return;
    }
    if (meta.cancel()) {
        // Cancel notification: mark the in-flight server call canceled
        // (stale-safe — a completed call's registry entry is gone).
        server_call::Cancel(msg->socket_id, meta.correlation_id());
        return;
    }
    if (meta.desc_ack()) {
        // Response-descriptor completion (ISSUE 12): the client finished
        // reading the descriptor we answered correlation_id with — drop
        // the pin. Scoped to the delivering connection (correlation ids
        // are only unique per client process) and exactly-once through
        // the lease registry: a duplicate or post-reap ack finds nothing
        // and is a no-op. Token-carrying acks release by direct lookup
        // (still call+connection validated); token-less acks pay the
        // ledger scan.
        if (meta.has_desc_ack_token() && meta.desc_ack_token() != 0) {
            block_lease::ReleaseAcked(meta.desc_ack_token(),
                                      meta.correlation_id(),
                                      (uint64_t)msg->socket_id);
        } else {
            block_lease::ReleaseByCall(meta.correlation_id(),
                                       (uint64_t)msg->socket_id);
        }
        rsp_desc::CountAck();
        return;
    }
    if (meta.has_window_grant()) {
        // Verb window grant exchange (ISSUE 18): REQUEST carves + pins
        // a window and answers GRANT on the same connection; GRANT
        // wakes the RequestWindow waiter by correlation token. Both
        // are meta-only frames.
        const auto& wg = meta.window_grant();
        if (wg.kind() == 1) {
            verbs::WindowInfo info;
            const int rc = verbs::HandleGrantRequest(
                (uint64_t)msg->socket_id, wg.length(), wg.mode(),
                wg.has_lease_ms() ? wg.lease_ms() : 0, &info);
            rpc::RpcMeta rsp;
            rsp.set_correlation_id(meta.correlation_id());
            auto* out = rsp.mutable_window_grant();
            out->set_kind(2);  // GRANT
            if (rc != 0) {
                out->set_status(rc);
            } else {
                out->set_window_id(info.window_id);
                out->set_pool_id(info.pool_id);
                out->set_offset(info.offset);
                out->set_length(info.length);
                out->set_pool_epoch(info.epoch);
                out->set_mode(info.mode);
                out->set_lease_ms(info.lease_ms);
            }
            IOBuf meta_buf;
            SerializePbToIOBuf(rsp, &meta_buf);
            IOBuf frame;
            PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
            SocketUniquePtr s;
            if (Socket::AddressSocket(msg->socket_id, &s) == 0) {
                s->Write(&frame);
            }
        } else {
            verbs::WindowInfo info;
            info.window_id = wg.window_id();
            info.pool_id = wg.pool_id();
            info.offset = wg.offset();
            info.length = wg.length();
            info.epoch = wg.pool_epoch();
            info.mode = wg.mode();
            info.lease_ms = wg.lease_ms();
            verbs::HandleGrantResponse(meta.correlation_id(),
                                       wg.status(), info);
        }
        return;
    }
    if (meta.has_verb_post()) {
        // Emulated two-sided verb at the TARGET (ISSUE 18): validate
        // against the granted window (epoch/lease/bounds/crc) and
        // answer a completion frame — READ's bytes ride back as its
        // body. A stale window answers TERR_STALE_EPOCH in the
        // completion status; the connection never fails.
        const auto& vp = meta.verb_post();
        IOBuf back;
        uint32_t crc = 0;
        const int rc = verbs::HandleWireVerb(
            (int)vp.op(), vp.wr_id(), vp.window_id(), vp.offset(),
            vp.length(), vp.pool_epoch(), vp.crc32c(), msg->body, &back,
            &crc);
        rpc::RpcMeta rsp;
        auto* vc = rsp.mutable_verb_completion();
        vc->set_wr_id(vp.wr_id());
        if (rc != 0) {
            vc->set_status(rc);
            back.clear();
        } else {
            vc->set_bytes(vp.length());
            if (!back.empty()) vc->set_crc32c(crc);
        }
        IOBuf meta_buf;
        SerializePbToIOBuf(rsp, &meta_buf);
        IOBuf frame;
        PackTpuStdFrame(&frame, meta_buf, back, IOBuf());
        SocketUniquePtr s;
        if (Socket::AddressSocket(msg->socket_id, &s) == 0) {
            s->Write(&frame);
        }
        return;
    }
    if (meta.has_verb_completion()) {
        const auto& vc = meta.verb_completion();
        verbs::HandleWireCompletion(vc.wr_id(), (int)vc.status(),
                                    msg->body, vc.crc32c());
        return;
    }
    if (meta.has_stream_frame() && !meta.has_request() &&
        !meta.has_response()) {
        // Push-stream tier frame (ISSUE 17): DATA/ACK/CLOSE keyed by
        // stream_id, not correlation_id. DATA's chunk bytes are the
        // frame body. Unknown kinds fail the STREAM inside OnFrame,
        // never this connection.
        const auto& sf = meta.stream_frame();
        if (sf.has_pool_attachment() &&
            (sf.kind() == 0 || sf.kind() == 1)) {
            // Descriptor-carried DATA chunk (ISSUE 18 satellite):
            // resolve the reference in place (scope -> registry ->
            // epoch -> crc, same fences as request descriptors), copy
            // into the frame body the stream layer expects, and ack so
            // the sender's pin drops. Any failure drops the FRAME only
            // — the stream's gap-NAK retransmit recovers the chunk
            // inline, and the sender's reaper frees the orphan pin.
            const auto& pd = sf.pool_attachment();
            bool ok = false;
            SocketUniquePtr s;
            if (Socket::AddressSocket(msg->socket_id, &s) == 0 &&
                TransportDescriptorScopeOk(s.get(), pd.pool_id())) {
                const char* base = nullptr;
                size_t size = 0;
                uint64_t ep = 0;
                if (pool_registry::Resolve(pd.pool_id(), &base, &size,
                                           &ep) &&
                    pd.offset() <= size &&
                    pd.length() <= size - pd.offset() &&
                    (!pd.has_pool_epoch() || pd.pool_epoch() == 0 ||
                     pd.pool_epoch() == ep) &&
                    (!pd.has_crc32c() ||
                     crc32c_extend(0, base + pd.offset(),
                                   pd.length()) == pd.crc32c())) {
                    msg->body.clear();
                    msg->body.append(base + pd.offset(),
                                     (size_t)pd.length());
                    *g_stream_desc_resolves << 1;
                    transport_stats::AddDescIn(s->transport_tier(),
                                               (int64_t)pd.length());
                    SendTpuStdDescAck(msg->socket_id, sf.seq(),
                                      pd.ack_token());
                    ok = true;
                }
            }
            if (!ok) {
                *g_stream_desc_rejects << 1;
                return;
            }
        }
        push_stream::OnFrame(msg->socket_id, sf.stream_id(),
                             sf.kind() == 0 ? 1 : sf.kind(), sf.seq(),
                             sf.flags(), sf.ack_seq(), sf.credits(),
                             sf.error_code(), &msg->body);
        return;
    }
    if (meta.has_request()) {
        ProcessTpuStdRequest(msg.get(), meta);
    } else {
        ProcessTpuStdResponse(msg.get(), meta);
    }
}

void GlobalInitializeOrDie() {
    static std::once_flag once;
    std::call_once(once, [] {
        // A peer closing mid-write must surface as EPIPE from the write,
        // not kill the process (reference global.cpp:333-337 ignores
        // SIGPIPE the same way; first bitten here by SSL_write on a
        // connection curl had already torn down). Respect a handler the
        // application installed itself.
        struct sigaction oldact;
        if (sigaction(SIGPIPE, nullptr, &oldact) != 0 ||
            (oldact.sa_handler == nullptr &&
             oldact.sa_sigaction == nullptr)) {
            CHECK(SIG_ERR != signal(SIGPIPE, SIG_IGN));
        }
        // Connection death cancels the server calls still in flight on
        // it (the observer hops to a fresh fiber before running any
        // cancellation, so SetFailed's callers never execute user code).
        Socket::set_failure_observer(&server_call::OnSocketFailed);
        // Epoch-fence + response-direction descriptor + transport-tier
        // families visible from the first scrape (lint contract: a
        // 0-valued counter is data; a missing one is not).
        *g_pool_epoch_rejects << 0;
        *g_rsp_desc_sends << 0;
        *g_rsp_desc_send_bytes << 0;
        *g_rsp_desc_fallbacks << 0;
        *g_rsp_desc_resolves << 0;
        *g_rsp_desc_resolve_bytes << 0;
        *g_rsp_desc_rejects << 0;
        *g_rsp_desc_acks << 0;
        *g_stream_desc_chunks << 0;
        *g_stream_desc_fallbacks << 0;
        *g_stream_desc_resolves << 0;
        *g_stream_desc_rejects << 0;
        transport_stats::ExposeVars();
        push_stream::ExposeVars();
        // One-sided verb plane (ISSUE 18): the pb-free tici layer moves
        // data; the wire seams (grant exchange + emulated two-sided
        // fallback) live here where the pb runtime is.
        verbs::SetGrantRequestSender(&SendVerbGrantRequest);
        verbs::SetVerbWireSender(&SendVerbWire);
        verbs::SetOneSidedProbe(&VerbOneSidedProbe);
        verbs::SetSglMaxProbe(&VerbSglMaxProbe);
        verbs::ExposeVars();
        Protocol p;
        p.parse = ParseTpuStdMessage;
        p.process = ProcessTpuStdMessage;
        p.name = "tpu_std";
        // Run-to-completion (ISSUE 7): small frames process on the input
        // fiber (responses complete RPCs; requests still fan their
        // handler out unless the method is flagged inline-safe), and the
        // 12-byte header peek skips the cut/re-parse loop on sticky
        // connections.
        p.inline_safe = true;
        p.peek = PeekTpuStdFrame;
        p.peek_len = kHeaderLen;
        g_tpu_std_index = RegisterProtocol(p);
        stream_internal::RegisterStreamProtocolOrDie();
        RegisterIciHandshakeProtocol();
        RegisterHttp2Protocol();
        RegisterHttp2ClientProtocol();
        RegisterHttpProtocol();
        RegisterRedisProtocols();
    });
}

}  // namespace tpurpc
