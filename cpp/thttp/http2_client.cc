#include "thttp/http2_client.h"

#include <arpa/inet.h>

#include <algorithm>
#include <cstring>
#include <map>
#include <mutex>
#include <vector>

#include "tbase/errno.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tfiber/butex.h"
#include "tfiber/fiber.h"
#include "thttp/h2_frames.h"
#include "thttp/hpack.h"
#include "tnet/input_messenger.h"
#include "tnet/protocol.h"
#include "trpc/controller.h"

namespace tpurpc {

using namespace h2;

namespace {

constexpr size_t kMaxRespBody = 64u << 20;
constexpr size_t kMaxHeaderBlock = 64u << 10;

int g_h2_client_index = -1;

// Per-connection client session, installed as the socket's conn_data
// BEFORE the first write, so response parsing can claim the bytes.
struct H2ClientSession {
    std::mutex mu;
    HpackDecoder decoder;           // response header blocks
    uint32_t next_stream_id = 1;    // odd, increasing (RFC 7540 §5.1.1)
    bool preface_sent = false;
    int64_t conn_send_window = kDefaultWindow;
    int64_t peer_initial_window = kDefaultWindow;
    void* window_butex = butex_create();

    struct RespStream {
        uint64_t cid;
        std::vector<HpackHeader> headers;   // response HEADERS
        std::vector<HpackHeader> trailers;  // trailing HEADERS
        IOBuf body;
        bool has_headers = false;
        int64_t send_window = kDefaultWindow;
    };
    std::map<uint32_t, RespStream> streams;

    uint32_t cont_stream = 0;  // CONTINUATION expected for this stream
    uint8_t cont_flags = 0;
    std::string header_block;

    ~H2ClientSession() { butex_destroy(window_butex); }

    void WakeWindowWaiters() {
        butex_word(window_butex)->fetch_add(1, std::memory_order_release);
        butex_wake_all(window_butex);
    }
};

void FailAllStreams(H2ClientSession* sess, int error);

// Runs at socket recycle (last ref dropped — no fiber can still touch
// the connection): pending calls learn their connection died here; until
// then their RPC timeouts cover the gap, like tpu_std responses on a
// dead socket.
void DeleteClientSession(void* s) {
    auto* sess = (H2ClientSession*)s;
    FailAllStreams(sess, TERR_FAILED_SOCKET);
    delete sess;
}

H2ClientSession* client_session_of(Socket* s) {
    // Only sockets we marked at send time carry a client session; the
    // preferred-protocol check makes the conn_data cast safe (a server
    // h2 socket stores an H2Session under a different protocol index).
    if (s->preferred_protocol_index != g_h2_client_index) return nullptr;
    return (H2ClientSession*)s->conn_data();
}

const std::string* FindHeader(const std::vector<HpackHeader>& hs,
                              const char* name) {
    for (const auto& h : hs) {
        if (h.name == name) return &h.value;
    }
    return nullptr;
}

// Fail every pending stream of the session (connection died / GOAWAY).
// Errors go through id_error, which QUEUES when the id is locked: this
// can run at socket recycle on the stack of whoever dropped the last
// ref — including the RPC's own IssueRPC, which HOLDS the id lock
// (blocking on it here deadlocked: IssueRPC -> Dereference -> OnRecycle
// -> DeleteClientSession -> this -> id_lock_range on the same id).
void FailAllStreams(H2ClientSession* sess, int error) {
    std::vector<uint64_t> cids;
    {
        std::lock_guard<std::mutex> g(sess->mu);
        for (auto& kv : sess->streams) cids.push_back(kv.second.cid);
        sess->streams.clear();
    }
    for (uint64_t cid : cids) {
        id_error(cid, error);
    }
}

// ---------------- response completion ----------------

// Map grpc-status (trailers) / :status to the RPC result and finish.
void CompleteStream(H2ClientSession::RespStream&& st) {
    const std::string* status = FindHeader(st.headers, ":status");
    // Trailers-only responses put grpc-status in the first (only) block.
    const std::string* grpc_status = FindHeader(st.trailers, "grpc-status");
    if (grpc_status == nullptr) {
        grpc_status = FindHeader(st.headers, "grpc-status");
    }
    const std::string* grpc_msg = FindHeader(st.trailers, "grpc-message");
    if (grpc_msg == nullptr) {
        grpc_msg = FindHeader(st.headers, "grpc-message");
    }
    if (status != nullptr && *status != "200") {
        CompleteClientUnaryResponse(st.cid, TERR_RESPONSE,
                                    "h2 :status " + *status, nullptr);
        return;
    }
    if (grpc_status != nullptr && *grpc_status != "0") {
        CompleteClientUnaryResponse(
            st.cid, TERR_RESPONSE,
            "grpc-status " + *grpc_status +
                (grpc_msg != nullptr ? ": " + *grpc_msg : std::string()),
            nullptr);
        return;
    }
    // gRPC unary body: 1-byte compressed flag + u32be length + pb.
    if (st.body.size() < 5) {
        CompleteClientUnaryResponse(st.cid, TERR_RESPONSE,
                                    "short grpc response body", nullptr);
        return;
    }
    char prefix[5];
    st.body.cutn(prefix, 5);
    if (prefix[0] != 0) {
        CompleteClientUnaryResponse(st.cid, TERR_RESPONSE,
                                    "compressed grpc response unsupported",
                                    nullptr);
        return;
    }
    uint32_t len;
    memcpy(&len, prefix + 1, 4);
    len = ntohl(len);
    if ((size_t)len != st.body.size()) {
        CompleteClientUnaryResponse(st.cid, TERR_RESPONSE,
                                    "grpc length prefix mismatch", nullptr);
        return;
    }
    CompleteClientUnaryResponse(st.cid, 0, "", &st.body);
}

void* CompleteStreamThunk(void* arg) {
    auto* st = (H2ClientSession::RespStream*)arg;
    CompleteStream(std::move(*st));
    delete st;
    return nullptr;
}

// Hand the completion to a background fiber — NEVER complete inline from
// the in-order input fiber. CompleteClientUnaryResponse blocks in
// id_lock_range; the lock may be held by this very stream's SENDER parked
// on h2 flow control (H2ClientSendUnary waits for WINDOW_UPDATEs that
// only this input fiber can deliver). Observed deadlock: early
// trailers-only response to a >64KB request — the response completes
// while the sender still holds the CallId lock waiting for window that
// never comes (the server already finished the stream). Same discipline
// as Socket::CloseFdAndDropQueued's id_error fiber hand-off.
void CompleteStreamInBackground(H2ClientSession::RespStream&& st) {
    auto* heap = new H2ClientSession::RespStream(std::move(st));
    fiber_t tid;
    if (fiber_start_background(&tid, nullptr, CompleteStreamThunk, heap) !=
        0) {
        // Out of fibers: inline is the lesser evil (the deadlock needs a
        // concurrently parked sender; a fiber-exhausted process has
        // bigger problems and the RPC deadline still bounds it).
        CompleteStream(std::move(*heap));
        delete heap;
    }
}

// ---------------- frame processing (input fiber, in order) ----------------

class H2ClientFrame : public InputMessageBase {
public:
    uint8_t type = 0;
    uint8_t flags = 0;
    uint32_t stream_id = 0;
    IOBuf payload;
};

void HandleHeaderBlockDone(Socket* s, H2ClientSession* sess,
                           uint32_t stream_id, uint8_t flags) {
    std::vector<HpackHeader> headers;
    if (!sess->decoder.Decode((const uint8_t*)sess->header_block.data(),
                              sess->header_block.size(), &headers)) {
        s->SetFailedWithError(TERR_RESPONSE);  // COMPRESSION_ERROR
        return;
    }
    sess->header_block.clear();
    if (stream_id == 0) return;
    const bool complete = (flags & kFlagEndStream) != 0;
    H2ClientSession::RespStream done;
    bool finish = false;
    {
        std::lock_guard<std::mutex> g(sess->mu);
        auto it = sess->streams.find(stream_id);
        if (it == sess->streams.end()) return;  // canceled/unknown
        H2ClientSession::RespStream& st = it->second;
        if (!st.has_headers) {
            st.headers = std::move(headers);
            st.has_headers = true;
        } else {
            st.trailers = std::move(headers);
        }
        if (complete) {
            done = std::move(st);
            sess->streams.erase(it);
            finish = true;
        }
    }
    if (finish) CompleteStreamInBackground(std::move(done));
}

void ProcessH2ClientFrame(InputMessageBase* raw) {
    std::unique_ptr<H2ClientFrame> msg((H2ClientFrame*)raw);
    SocketUniquePtr s = SocketUniquePtr::FromId(msg->socket_id);
    if (!s) return;
    H2ClientSession* sess = client_session_of(s.get());
    if (sess == nullptr) return;

    // CONTINUATION discipline (same as the server side).
    if (sess->cont_stream != 0 && (msg->type != H2_CONTINUATION ||
                                   msg->stream_id != sess->cont_stream)) {
        s->SetFailedWithError(TERR_RESPONSE);
        return;
    }

    switch (msg->type) {
        case H2_SETTINGS: {
            if (msg->flags & kFlagAck) break;
            const std::string p = msg->payload.to_string();
            for (size_t off = 0; off + 6 <= p.size(); off += 6) {
                uint16_t id;
                uint32_t value;
                memcpy(&id, p.data() + off, 2);
                memcpy(&value, p.data() + off + 2, 4);
                id = ntohs(id);
                value = ntohl(value);
                if (id == 0x4) {  // SETTINGS_INITIAL_WINDOW_SIZE
                    std::lock_guard<std::mutex> g(sess->mu);
                    const int64_t delta =
                        (int64_t)value - sess->peer_initial_window;
                    sess->peer_initial_window = value;
                    for (auto& kv : sess->streams) {
                        kv.second.send_window += delta;
                    }
                    sess->WakeWindowWaiters();
                }
            }
            IOBuf ack;
            ack.append(BuildFrame(H2_SETTINGS, kFlagAck, 0, ""));
            s->Write(&ack);
            break;
        }
        case H2_PING: {
            if (msg->flags & kFlagAck) break;
            IOBuf ack;
            ack.append(BuildFrame(H2_PING, kFlagAck, 0,
                                  msg->payload.to_string()));
            s->Write(&ack);
            break;
        }
        case H2_WINDOW_UPDATE: {
            if (msg->payload.size() != 4) break;
            uint32_t inc;
            msg->payload.copy_to(&inc, 4);
            inc = ntohl(inc) & 0x7fffffffu;
            std::lock_guard<std::mutex> g(sess->mu);
            if (msg->stream_id == 0) {
                sess->conn_send_window += inc;
            } else {
                auto it = sess->streams.find(msg->stream_id);
                if (it != sess->streams.end()) {
                    it->second.send_window += inc;
                }
            }
            sess->WakeWindowWaiters();
            break;
        }
        case H2_HEADERS: {
            IOBuf frag = std::move(msg->payload);
            if (msg->flags & kFlagPadded) {
                uint8_t pad;
                if (frag.size() < 1 || ((void)frag.cutn(&pad, 1),
                                        (size_t)pad > frag.size())) {
                    s->SetFailedWithError(TERR_RESPONSE);
                    return;
                }
                IOBuf tmp;
                frag.cutn(&tmp, frag.size() - pad);
                frag.swap(tmp);
            }
            if (msg->flags & kFlagPriority) {
                if (frag.size() < 5) {
                    s->SetFailedWithError(TERR_RESPONSE);
                    return;
                }
                IOBuf drop;
                frag.cutn(&drop, 5);
            }
            sess->header_block += frag.to_string();
            if (sess->header_block.size() > kMaxHeaderBlock) {
                s->SetFailedWithError(TERR_RESPONSE);
                return;
            }
            if (msg->flags & kFlagEndHeaders) {
                HandleHeaderBlockDone(s.get(), sess, msg->stream_id,
                                      msg->flags);
            } else {
                sess->cont_stream = msg->stream_id;
                sess->cont_flags = msg->flags;
            }
            break;
        }
        case H2_CONTINUATION: {
            if (sess->cont_stream == 0) {
                s->SetFailedWithError(TERR_RESPONSE);
                return;
            }
            sess->header_block += msg->payload.to_string();
            if (sess->header_block.size() > kMaxHeaderBlock) {
                s->SetFailedWithError(TERR_RESPONSE);
                return;
            }
            if (msg->flags & kFlagEndHeaders) {
                const uint8_t hf = sess->cont_flags;
                sess->cont_stream = 0;
                HandleHeaderBlockDone(s.get(), sess, msg->stream_id, hf);
            }
            break;
        }
        case H2_DATA: {
            const size_t sz = msg->payload.size();
            IOBuf frag = std::move(msg->payload);
            if (msg->flags & kFlagPadded) {
                uint8_t pad;
                if (frag.size() < 1 || ((void)frag.cutn(&pad, 1),
                                        (size_t)pad > frag.size())) {
                    s->SetFailedWithError(TERR_RESPONSE);
                    return;
                }
                IOBuf tmp;
                frag.cutn(&tmp, frag.size() - pad);
                frag.swap(tmp);
            }
            H2ClientSession::RespStream done;
            bool finish = false;
            bool known = false;
            {
                std::lock_guard<std::mutex> g(sess->mu);
                auto it = sess->streams.find(msg->stream_id);
                if (it != sess->streams.end()) {
                    known = true;
                    it->second.body.append(frag);
                    if (it->second.body.size() > kMaxRespBody) {
                        s->SetFailedWithError(TERR_RESPONSE);
                        return;
                    }
                    if (msg->flags & kFlagEndStream) {
                        done = std::move(it->second);
                        sess->streams.erase(it);
                        finish = true;
                    }
                }
            }
            // Replenish receive windows (conn always; stream while open).
            if (sz > 0) {
                uint32_t inc = htonl((uint32_t)sz);
                std::string p((const char*)&inc, 4);
                std::string out = BuildFrame(H2_WINDOW_UPDATE, 0, 0, p);
                if (known && !finish) {
                    out += BuildFrame(H2_WINDOW_UPDATE, 0, msg->stream_id,
                                      p);
                }
                IOBuf buf;
                buf.append(out);
                s->Write(&buf);
            }
            if (finish) CompleteStreamInBackground(std::move(done));
            break;
        }
        case H2_RST_STREAM: {
            uint64_t cid = 0;
            {
                std::lock_guard<std::mutex> g(sess->mu);
                auto it = sess->streams.find(msg->stream_id);
                if (it == sess->streams.end()) break;
                cid = it->second.cid;
                sess->streams.erase(it);
            }
            // REFUSED_STREAM (RFC 9113 §8.7) guarantees the server did
            // no processing: retriable on another connection without
            // spending retry budget (a draining server refuses streams
            // that raced its GOAWAY). Every other code means unknown
            // progress — plain TERR_RESPONSE, budget applies.
            uint32_t rst_code = 0;
            if (msg->payload.size() >= 4) {
                msg->payload.copy_to(&rst_code, 4);
                rst_code = ntohl(rst_code);
            }
            // id_error (queues under a held lock): the id may be locked
            // by its sender parked mid-send on flow control; blocking
            // this in-order input fiber on it would stall the whole
            // connection's frame processing.
            id_error(cid, rst_code == 0x7 ? TERR_DRAINING : TERR_RESPONSE);
            break;
        }
        case H2_GOAWAY: {
            // Planned drain, not death — but ONLY for NO_ERROR. An error
            // GOAWAY (ENHANCE_YOUR_CALM, PROTOCOL_ERROR, ...) is the
            // server rejecting us: treat it like connection death so the
            // retries it causes DO consume budget (a shedding server
            // must not receive a budget-free re-issue storm).
            uint32_t last_id = 0;
            uint32_t error_code = 0;
            if (msg->payload.size() >= 8) {
                uint32_t words[2];
                msg->payload.copy_to(words, 8);
                last_id = ntohl(words[0]) & 0x7fffffffu;
                error_code = ntohl(words[1]);
            } else if (msg->payload.size() >= 4) {
                msg->payload.copy_to(&last_id, 4);
                last_id = ntohl(last_id) & 0x7fffffffu;
            }
            if (error_code != 0) {
                // The socket first: a caller woken by its stream's error
                // must not find the rejected connection still live.
                s->SetFailedWithError(TERR_FAILED_SOCKET);
                FailAllStreams(sess, TERR_FAILED_SOCKET);
                break;
            }
            // NO_ERROR: the server promises to answer every stream at or
            // below last-stream-id — those stay pending and complete
            // normally. Streams above it were provably NOT processed:
            // fail them as TERR_DRAINING, which is retriable on another
            // connection WITHOUT consuming retry budget (re-issuing
            // cannot load a server that is leaving). The socket is
            // marked draining (not failed) so the channel re-creates its
            // pinned connection for new calls while the old one
            // finishes; the server's eventual close fails whatever is
            // left through DeleteClientSession.
            std::vector<uint64_t> unprocessed;
            {
                std::lock_guard<std::mutex> g(sess->mu);
                for (auto it = sess->streams.begin();
                     it != sess->streams.end();) {
                    if (it->first > last_id) {
                        unprocessed.push_back(it->second.cid);
                        it = sess->streams.erase(it);
                    } else {
                        ++it;
                    }
                }
            }
            s->SetDraining();
            // id_error queues under a held id lock (same discipline as
            // RST_STREAM above): never block this in-order input fiber.
            for (uint64_t cid : unprocessed) {
                id_error(cid, TERR_DRAINING);
            }
            break;
        }
        default:
            break;
    }
}

ParseResult ParseH2ClientFrames(IOBuf* source, Socket* socket,
                                bool read_eof, const void* arg) {
    if (client_session_of(socket) == nullptr) {
        return ParseResult::make(ParseError::TRY_OTHERS);
    }
    if (source->size() < kFrameHeaderLen) {
        return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
    }
    char header[kFrameHeaderLen];
    source->copy_to(header, kFrameHeaderLen);
    const uint32_t len = ((uint32_t)(uint8_t)header[0] << 16) |
                         ((uint32_t)(uint8_t)header[1] << 8) |
                         (uint32_t)(uint8_t)header[2];
    if (len > kMaxFrameSize + 255) {
        return ParseResult::make(ParseError::ERROR);
    }
    if (source->size() < kFrameHeaderLen + len) {
        return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
    }
    source->pop_front(kFrameHeaderLen);
    auto* msg = new H2ClientFrame;
    msg->type = (uint8_t)header[3];
    msg->flags = (uint8_t)header[4];
    uint32_t sid;
    memcpy(&sid, header + 5, 4);
    msg->stream_id = ntohl(sid) & 0x7fffffffu;
    source->cutn(&msg->payload, len);
    return ParseResult::make_ok(msg);
}

}  // namespace

void H2ClientCancel(SocketId sid, uint64_t cid) {
    SocketUniquePtr s;
    if (Socket::AddressSocket(sid, &s) != 0) return;
    H2ClientSession* sess = client_session_of(s.get());
    if (sess == nullptr) return;
    uint32_t stream_id = 0;
    {
        std::lock_guard<std::mutex> g(sess->mu);
        for (auto it = sess->streams.begin(); it != sess->streams.end();
             ++it) {
            if (it->second.cid == cid) {
                stream_id = it->first;
                sess->streams.erase(it);
                break;
            }
        }
    }
    if (stream_id == 0) return;  // already completed / never sent
    uint32_t code = htonl(0x8);  // CANCEL
    IOBuf rst;
    rst.append(BuildFrame(H2_RST_STREAM, 0, stream_id,
                          std::string((const char*)&code, 4)));
    s->Write(&rst);
}

// ---------------- send path ----------------

int H2ClientSendUnary(Socket* s, uint64_t cid, const std::string& grpc_path,
                      const std::string& authority, const IOBuf& request_pb,
                      int64_t deadline_us, const std::string& authorization,
                      const std::string& tenant, int priority,
                      const std::string& session) {
    if (g_h2_client_index < 0) return -1;
    H2ClientSession* sess = client_session_of(s);
    std::string out;
    if (sess == nullptr) {
        // First RPC on this connection: install the session + preface.
        // IssueRPC serializes per-socket via the CallId lock only for one
        // call; two fibers may race here, so install under a plain
        // compare: set_conn_data is not atomic — but both racers run on
        // the SAME channel's first calls, which the SocketMap serializes
        // through connect-on-first-write ordering. Guard anyway with a
        // session-level mutex via double-checked conn_data.
        static std::mutex install_mu;
        std::lock_guard<std::mutex> g(install_mu);
        sess = client_session_of(s);
        if (sess == nullptr) {
            sess = new H2ClientSession;
            s->set_conn_data(sess, DeleteClientSession);
            s->preferred_protocol_index = g_h2_client_index;
        }
    }
    // HEADERS: gRPC request pseudo-headers + metadata (built before the
    // lock; the block itself doesn't depend on the stream id).
    std::vector<std::pair<std::string, std::string>> headers = {
        {":method", "POST"},
        {":scheme", "http"},
        {":path", grpc_path},
        {":authority", authority.empty() ? "tpurpc" : authority},
        {"content-type", "application/grpc"},
        {"te", "trailers"},
    };
    if (!authorization.empty()) {
        headers.emplace_back("authorization", authorization);
    }
    // QoS identity (ISSUE 8): the h2 spelling of the tpu_std meta's
    // tenant/priority pair.
    if (!tenant.empty()) {
        headers.emplace_back("x-tpu-tenant", tenant);
    }
    if (priority >= 0) {
        headers.emplace_back("x-tpu-priority", std::to_string(priority));
    }
    // Sticky-session identity (ISSUE 16).
    if (!session.empty()) {
        headers.emplace_back("x-tpu-session", session);
    }
    if (deadline_us > 0) {
        const int64_t remain_us = deadline_us - monotonic_time_us();
        if (remain_us > 0) {
            // Floor at 1ms while budget remains (see the tpu_std stamp
            // in IssueRPC: 0 means "already expired"). The gRPC spec
            // caps the value at 8 digits — upscale the unit for huge
            // deadlines (truncation only SHRINKS the budget: safe).
            const int64_t remain_ms =
                remain_us < 1000 ? 1 : remain_us / 1000;
            std::string gt;
            if (remain_ms <= 99999999) {
                gt = std::to_string(remain_ms) + "m";
            } else if (remain_ms / 1000 <= 99999999) {
                gt = std::to_string(remain_ms / 1000) + "S";
            } else if (remain_ms / 60000 <= 99999999) {
                gt = std::to_string(remain_ms / 60000) + "M";
            } else {
                gt = std::to_string(std::min<int64_t>(
                         99999999, remain_ms / 3600000)) +
                     "H";
            }
            headers.emplace_back("grpc-timeout", gt);
        } else {
            // Budget already spent: say so explicitly ("1n" parses to 0)
            // so the server sheds instead of executing for nobody.
            headers.emplace_back("grpc-timeout", "1n");
        }
    }

    uint32_t stream_id;
    {
        // Allocate the stream id AND queue preface+HEADERS under ONE mu
        // hold: ids must hit the wire in increasing order (RFC 7540
        // §5.1.1 — a reordered HEADERS is a connection error) and the
        // preface must precede everything. Socket::Write never blocks,
        // so holding mu across it is safe; DATA goes out separately
        // below (inter-stream DATA interleaving is legal).
        std::lock_guard<std::mutex> g(sess->mu);
        if (!sess->preface_sent) {
            out.append(kPreface, kPrefaceLen);
            out += BuildFrame(H2_SETTINGS, 0, 0, "");
            sess->preface_sent = true;
        }
        stream_id = sess->next_stream_id;
        sess->next_stream_id += 2;
        auto& st = sess->streams[stream_id];
        st.cid = cid;
        st.send_window = sess->peer_initial_window;
        AppendHeadersFrames(&out, kFlagEndHeaders, stream_id,
                            EncodeHeaderBlock(headers));
        IOBuf hb;
        hb.append(out);
        out.clear();
        if (s->Write(&hb, cid) != 0) {
            sess->streams.erase(stream_id);
            return -1;
        }
    }

    // Cleanup for send-side failures below: drop our stream entry and
    // RST it so the server releases its half-open state too.
    auto abort_stream = [&]() {
        {
            std::lock_guard<std::mutex> g(sess->mu);
            sess->streams.erase(stream_id);
        }
        uint32_t code = htonl(0x8);  // CANCEL
        IOBuf rst;
        rst.append(BuildFrame(H2_RST_STREAM, 0, stream_id,
                              std::string((const char*)&code, 4)));
        s->Write(&rst);
    };

    // DATA: 5-byte gRPC prefix + pb, chunked to the frame cap. Unary
    // requests are bounded by the peer's default 64KB window in practice;
    // larger bodies park on WINDOW_UPDATE below.
    std::string body;
    body.push_back('\0');
    const uint32_t len = htonl((uint32_t)request_pb.size());
    body.append((const char*)&len, 4);
    body += request_pb.to_string();

    size_t sent = 0;
    const int64_t stall_deadline =
        deadline_us > 0 ? deadline_us
                        : monotonic_time_us() + 60 * 1000 * 1000;
    while (sent < body.size()) {
        // Snapshot before the window check (lost-wakeup guard — see the
        // server's WriteResponse loop).
        std::atomic<int>* word = butex_word(sess->window_butex);
        const int expected = word->load(std::memory_order_acquire);
        size_t n = 0;
        {
            std::lock_guard<std::mutex> g(sess->mu);
            auto it = sess->streams.find(stream_id);
            if (it == sess->streams.end()) return -1;  // already failed
            const int64_t avail = std::min<int64_t>(
                sess->conn_send_window, it->second.send_window);
            n = (size_t)std::max<int64_t>(
                0, std::min<int64_t>(
                       avail, (int64_t)std::min<size_t>(
                                  kMaxFrameSize, body.size() - sent)));
            if (n > 0) {
                sess->conn_send_window -= (int64_t)n;
                it->second.send_window -= (int64_t)n;
            }
        }
        if (n == 0) {
            if (!out.empty()) {
                IOBuf buf;
                buf.append(out);
                out.clear();
                if (s->Write(&buf) != 0) {
                    abort_stream();
                    return -1;
                }
            }
            if (s->Failed() || monotonic_time_us() >= stall_deadline) {
                abort_stream();
                return -1;
            }
            const int64_t abst = monotonic_time_us() + 1000 * 1000;
            butex_wait(sess->window_butex, expected, &abst);
            continue;
        }
        const bool last = sent + n >= body.size();
        AppendFrame(&out, H2_DATA, last ? kFlagEndStream : 0, stream_id,
                    body.data() + sent, n);
        sent += n;
    }
    IOBuf buf;
    buf.append(out);
    if (s->Write(&buf, cid) != 0) {
        abort_stream();
        return -1;
    }
    return 0;
}

void RegisterHttp2ClientProtocol() {
    if (g_h2_client_index >= 0) return;
    Protocol p;
    p.parse = ParseH2ClientFrames;
    p.process = ProcessH2ClientFrame;
    p.name = "h2c-client";
    p.process_in_order = true;  // shared HPACK decoder + session state
    g_h2_client_index = RegisterProtocol(p);
}

int Http2ClientProtocolIndex() { return g_h2_client_index; }

}  // namespace tpurpc
