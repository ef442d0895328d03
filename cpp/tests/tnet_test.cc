// I/O core loopback tests: real sockets, real epoll, full read/write paths —
// the in-process loopback style of the reference's tests (e.g.
// test/brpc_channel_unittest.cpp:195 starts a real listener in-process).
#include <sys/eventfd.h>
#include <sys/socket.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cstring>
#include <mutex>
#include <thread>
#include <vector>

#include "tbase/errno.h"
#include "tbase/flags.h"
#include "tbase/time.h"
#include "tfiber/fiber_sync.h"
#include "tnet/acceptor.h"
#include "tnet/event_dispatcher.h"
#include "tnet/input_messenger.h"
#include "tnet/socket.h"
#include "tnet/socket_map.h"
#include "ttest/ttest.h"

DECLARE_int32(inline_dispatch_budget);
DECLARE_int32(inline_dispatch_max_bytes);

using namespace tpurpc;

namespace {

// Test protocol: "TST0" + u32le length + payload.
constexpr char kMagic[4] = {'T', 'S', 'T', '0'};

struct TestMsg : public InputMessageBase {
    IOBuf payload;
};

ParseResult test_parse(IOBuf* source, Socket* s, bool read_eof,
                       const void* arg) {
    if (source->size() < 8) {
        char head[4];
        const size_t n = source->copy_to(head, 4);
        if (memcmp(head, kMagic, n) != 0) {
            return ParseResult::make(ParseError::TRY_OTHERS);
        }
        return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
    }
    char header[8];
    source->copy_to(header, 8);
    if (memcmp(header, kMagic, 4) != 0) {
        return ParseResult::make(ParseError::TRY_OTHERS);
    }
    uint32_t len;
    memcpy(&len, header + 4, 4);
    if (source->size() < 8 + (size_t)len) {
        return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
    }
    source->pop_front(8);
    auto* msg = new TestMsg;
    source->cutn(&msg->payload, len);
    msg->byte_size = 8 + (size_t)len;
    return ParseResult::make_ok(msg);
}

// Zero-cut peek for the test protocol (ISSUE 7): magic + total size from
// the contiguous 8-byte header.
int64_t test_peek(const char* hdr, Socket*) {
    if (memcmp(hdr, kMagic, 4) != 0) return 0;
    uint32_t len;
    memcpy(&len, hdr + 4, 4);
    if (len > (64u << 20)) return -1;
    return 8 + (int64_t)len;
}

void frame(IOBuf* out, const IOBuf& payload) {
    char header[8];
    memcpy(header, kMagic, 4);
    const uint32_t len = (uint32_t)payload.size();
    memcpy(header + 4, &len, 4);
    out->append(header, 8);
    out->append(payload);
}

// Where a test asks for them: each served message's stage-clock origin
// (InputMessageBase::consumed_us), in the order they were processed.
std::mutex g_consumed_mu;
std::vector<int64_t>* g_consumed = nullptr;

// Server side: echo the payload back.
void server_process(InputMessageBase* raw) {
    TestMsg* msg = (TestMsg*)raw;
    {
        std::lock_guard<std::mutex> g(g_consumed_mu);
        if (g_consumed != nullptr) g_consumed->push_back(msg->consumed_us);
    }
    SocketUniquePtr s;
    if (Socket::AddressSocket(msg->socket_id, &s) == 0) {
        IOBuf out;
        frame(&out, msg->payload);
        s->Write(&out);
    }
    delete msg;
}

// Client side: collect responses.
struct ClientSink {
    std::mutex mu;
    std::vector<std::string> responses;
    CountdownEvent pending{0};
};
ClientSink* g_sink = nullptr;

void client_process(InputMessageBase* raw) {
    TestMsg* msg = (TestMsg*)raw;
    {
        std::lock_guard<std::mutex> g(g_sink->mu);
        g_sink->responses.push_back(msg->payload.to_string());
    }
    g_sink->pending.signal();
    delete msg;
}

int g_server_proto = -1;
int g_client_proto = -1;

void register_test_protocols() {
    static std::once_flag once;
    std::call_once(once, [] {
        Protocol sp;
        sp.parse = test_parse;
        sp.process = server_process;
        sp.name = "test_echo_server";
        sp.inline_safe = true;  // echo-on-input-fiber: run-to-completion
        sp.peek = test_peek;
        sp.peek_len = 8;
        g_server_proto = RegisterProtocol(sp);
        Protocol cp;
        cp.parse = test_parse;
        cp.process = client_process;
        cp.name = "test_echo_client";
        cp.inline_safe = true;
        cp.peek = test_peek;
        cp.peek_len = 8;
        g_client_proto = RegisterProtocol(cp);
    });
}

// One served loopback connection driven by raw writes from this test:
// returns the ACCEPTED socket's echoes through `sink`.
struct EchoFixture {
    InputMessenger server_m;
    InputMessenger client_m;
    Acceptor acceptor;
    EndPoint server_ep;
    SocketId client_id = INVALID_VREF_ID;

    EchoFixture() : acceptor(&server_m) {
        register_test_protocols();
        server_m.add_protocol(g_server_proto);
        client_m.add_protocol(g_client_proto);
    }

    bool Start() {
        EndPoint listen_ep;
        str2endpoint("127.0.0.1:0", &listen_ep);
        if (acceptor.StartAccept(listen_ep) != 0) return false;
        str2endpoint("127.0.0.1", acceptor.listened_port(), &server_ep);
        return SocketMap::singleton()->GetOrCreate(server_ep, &client_m,
                                                   &client_id) == 0;
    }

    ~EchoFixture() {
        if (client_id != INVALID_VREF_ID) {
            Socket::SetFailedById(client_id);
            SocketMap::singleton()->Remove(server_ep, client_id);
        }
    }
};

}  // namespace

TEST(Net, LoopbackEchoSmallAndLarge) {
    register_test_protocols();
    ClientSink sink;
    g_sink = &sink;

    InputMessenger server_m({g_server_proto});
    Acceptor acceptor(&server_m);
    EndPoint listen_ep;
    str2endpoint("127.0.0.1:0", &listen_ep);
    ASSERT_EQ(acceptor.StartAccept(listen_ep), 0);
    ASSERT_GT(acceptor.listened_port(), 0);

    InputMessenger client_m({g_client_proto});
    EndPoint server_ep;
    str2endpoint("127.0.0.1", acceptor.listened_port(), &server_ep);
    SocketId cid;
    ASSERT_EQ(SocketMap::singleton()->GetOrCreate(server_ep, &client_m, &cid),
              0);

    SocketUniquePtr cs;
    ASSERT_EQ(Socket::AddressSocket(cid, &cs), 0);

    // Small message.
    {
        IOBuf payload;
        payload.append("hello tpu-rpc");
        IOBuf framed;
        frame(&framed, payload);
        sink.pending.reset(1);
        ASSERT_EQ(cs->Write(&framed), 0);
        ASSERT_EQ(sink.pending.wait(), 0);
        std::lock_guard<std::mutex> g(sink.mu);
        ASSERT_EQ(sink.responses.size(), 1u);
        EXPECT_EQ(sink.responses[0], "hello tpu-rpc");
        sink.responses.clear();
    }

    // Large (1MB) message exercising multi-block iobufs + partial writes.
    {
        std::string big(1 << 20, 'x');
        for (size_t i = 0; i < big.size(); ++i) big[i] = (char)('a' + i % 26);
        IOBuf payload;
        payload.append(big);
        IOBuf framed;
        frame(&framed, payload);
        sink.pending.reset(1);
        ASSERT_EQ(cs->Write(&framed), 0);
        ASSERT_EQ(sink.pending.wait(), 0);
        std::lock_guard<std::mutex> g(sink.mu);
        ASSERT_EQ(sink.responses.size(), 1u);
        EXPECT_TRUE(sink.responses[0] == big);
        sink.responses.clear();
    }

    // Burst of messages: ordering + batching through the write queue.
    {
        const int kN = 200;
        sink.pending.reset(kN);
        for (int i = 0; i < kN; ++i) {
            IOBuf payload;
            payload.append("msg-" + std::to_string(i));
            IOBuf framed;
            frame(&framed, payload);
            ASSERT_EQ(cs->Write(&framed), 0);
        }
        ASSERT_EQ(sink.pending.wait(), 0);
        std::lock_guard<std::mutex> g(sink.mu);
        ASSERT_EQ(sink.responses.size(), (size_t)kN);
        // Each request runs on its own fiber (reference QueueMessage), so
        // response ORDER is not guaranteed at this layer — correlation ids
        // provide matching at the RPC layer. Check the full set round-
        // tripped intact.
        std::vector<std::string> got = sink.responses;
        std::sort(got.begin(), got.end());
        std::vector<std::string> want;
        for (int i = 0; i < kN; ++i) want.push_back("msg-" + std::to_string(i));
        std::sort(want.begin(), want.end());
        EXPECT_TRUE(got == want);
        sink.responses.clear();
    }

    EXPECT_EQ(acceptor.accepted_count(), 1);  // one shared connection

    // Failure path: failed socket rejects writes.
    cs->SetFailedWithError(TERR_CLOSE);
    {
        IOBuf framed;
        frame(&framed, IOBuf());
        IOBuf copy = framed;
        EXPECT_EQ(cs->Write(&copy), -1);
        EXPECT_EQ(errno, TERR_FAILED_SOCKET);
    }
    SocketMap::singleton()->Remove(server_ep, cid);
    g_sink = nullptr;
}

TEST(Net, StaleSocketIdAddressFails) {
    SocketOptions opts;
    opts.fd = -1;
    str2endpoint("127.0.0.1:1", &opts.remote_side);
    SocketId id;
    ASSERT_EQ(Socket::Create(opts, &id), 0);
    SocketUniquePtr ptr;
    ASSERT_EQ(Socket::AddressSocket(id, &ptr), 0);
    ptr->SetFailed();
    SocketUniquePtr ptr2;
    EXPECT_EQ(Socket::AddressSocket(id, &ptr2), -1);
}

// ---- raw-speed round (ISSUE 7) ----

// Peek fast path: a frame whose header (and then body) is split across
// many tiny writes still cuts exactly once — the sticky connection waits
// peek-announced byte counts instead of re-parsing per read.
TEST(Net, PeekFastPathSplitHeaders) {
    ClientSink sink;
    g_sink = &sink;
    EchoFixture fx;
    ASSERT_TRUE(fx.Start());
    SocketUniquePtr cs;
    ASSERT_EQ(Socket::AddressSocket(fx.client_id, &cs), 0);

    // Whole first message: sniffs the protocol, the socket goes sticky.
    {
        IOBuf payload;
        payload.append("sniff");
        IOBuf framed;
        frame(&framed, payload);
        sink.pending.reset(1);
        ASSERT_EQ(cs->Write(&framed), 0);
        ASSERT_EQ(sink.pending.wait(), 0);
    }
    // Second message dribbled in 1-byte writes: 8 header bytes (split
    // peek), then the payload (split pending-frame wait).
    {
        const std::string body = "split-header-body";
        IOBuf payload;
        payload.append(body);
        IOBuf framed;
        frame(&framed, payload);
        std::string wire = framed.to_string();
        sink.pending.reset(1);
        for (size_t i = 0; i < wire.size(); ++i) {
            IOBuf one;
            one.append(&wire[i], 1);
            ASSERT_EQ(cs->Write(&one), 0);
            usleep(1000);  // separate reads: each byte is its own burst
        }
        ASSERT_EQ(sink.pending.wait(), 0);
        std::lock_guard<std::mutex> g(sink.mu);
        ASSERT_EQ(sink.responses.size(), 2u);
        EXPECT_EQ(sink.responses[1], body);
    }
    g_sink = nullptr;
}

// Stage clock: a message's clock starts at the read that brought ITS first
// bytes. A's head arrives alone; 150 ms later one write brings A's tail and
// B's head, so the buffer is not empty when B's bytes land; 150 ms later B's
// tail. B must not inherit A's stamp (it would read as consumed 150 ms
// before its sender wrote it).
TEST(Net, AMessageLeftBehindByACutStartsItsClockAtItsOwnBytes) {
    ClientSink sink;
    g_sink = &sink;
    std::vector<int64_t> consumed;
    {
        std::lock_guard<std::mutex> g(g_consumed_mu);
        g_consumed = &consumed;
    }
    EchoFixture fx;
    ASSERT_TRUE(fx.Start());
    SocketUniquePtr cs;
    ASSERT_EQ(Socket::AddressSocket(fx.client_id, &cs), 0);
    std::string wire;
    for (const char* body : {"message-a-body", "message-b-body"}) {
        IOBuf payload, framed;
        payload.append(body);
        frame(&framed, payload);
        wire += framed.to_string();
    }
    const size_t one = wire.size() / 2;  // both frames are the same size
    const size_t cuts[] = {0, one / 2, one + one / 2, wire.size()};
    sink.pending.reset(2);
    int64_t wrote_us[3];
    for (int i = 0; i < 3; ++i) {
        if (i > 0) usleep(150 * 1000);
        IOBuf piece;
        piece.append(wire.data() + cuts[i], cuts[i + 1] - cuts[i]);
        wrote_us[i] = monotonic_time_us();
        ASSERT_EQ(cs->Write(&piece), 0);
    }
    ASSERT_EQ(sink.pending.wait(), 0);
    {
        std::lock_guard<std::mutex> g(g_consumed_mu);
        g_consumed = nullptr;
    }
    g_sink = nullptr;
    ASSERT_EQ(consumed.size(), 2u);
    // Each origin lies at or after the write that carried the message's
    // first byte, and before the next write.
    EXPECT_GE(consumed[0], wrote_us[0]);
    EXPECT_LT(consumed[0], wrote_us[1]);
    EXPECT_GE(consumed[1], wrote_us[1]);
    EXPECT_LT(consumed[1], wrote_us[2]);
}

// A sticky socket whose next bytes are NOT the sticky protocol's resets
// and re-sniffs (TRY_OTHERS contract); with no other protocol claiming
// the bytes the stream is broken and the connection fails.
TEST(Net, PeekStickyResetOnParseError) {
    ClientSink sink;
    g_sink = &sink;
    EchoFixture fx;
    ASSERT_TRUE(fx.Start());
    SocketUniquePtr cs;
    ASSERT_EQ(Socket::AddressSocket(fx.client_id, &cs), 0);

    IOBuf payload;
    payload.append("ok");
    IOBuf framed;
    frame(&framed, payload);
    sink.pending.reset(1);
    ASSERT_EQ(cs->Write(&framed), 0);
    ASSERT_EQ(sink.pending.wait(), 0);  // sticky now

    IOBuf garbage;
    garbage.append("GARBAGE-not-a-frame");
    ASSERT_EQ(cs->Write(&garbage), 0);
    // Server fails its accepted connection; we observe the close as a
    // client-side failure (EOF).
    for (int i = 0; i < 500 && !cs->Failed(); ++i) {
        usleep(10000);
    }
    EXPECT_TRUE(cs->Failed());
    g_sink = nullptr;
}

// TRY_OTHERS fallback still works with the peek fast path in the set: a
// fresh connection sniffs past the peek-enabled protocol to another
// parser, and a sticky peek mismatch re-sniffs instead of failing.
TEST(Net, PeekTryOthersFallback) {
    register_test_protocols();
    // Second wire format on the same server: "ALT0" + u32le len, echoed
    // back as a TST0 frame so the client sink still collects it.
    static int alt_proto = -1;
    static std::once_flag once;
    std::call_once(once, [] {
        Protocol ap;
        ap.parse = [](IOBuf* source, Socket*, bool,
                      const void*) -> ParseResult {
            if (source->size() < 8) {
                char head[4];
                const size_t n = source->copy_to(head, 4);
                if (memcmp(head, "ALT0", n) != 0) {
                    return ParseResult::make(ParseError::TRY_OTHERS);
                }
                return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
            }
            char header[8];
            source->copy_to(header, 8);
            if (memcmp(header, "ALT0", 4) != 0) {
                return ParseResult::make(ParseError::TRY_OTHERS);
            }
            uint32_t len;
            memcpy(&len, header + 4, 4);
            if (source->size() < 8 + (size_t)len) {
                return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
            }
            source->pop_front(8);
            auto* msg = new TestMsg;
            source->cutn(&msg->payload, len);
            msg->byte_size = 8 + (size_t)len;
            return ParseResult::make_ok(msg);
        };
        ap.process = [](InputMessageBase* raw) {
            TestMsg* msg = (TestMsg*)raw;
            SocketUniquePtr s;
            if (Socket::AddressSocket(msg->socket_id, &s) == 0) {
                IOBuf out, marked;
                marked.append("alt:");
                marked.append(msg->payload);
                frame(&out, marked);
                s->Write(&out);
            }
            delete msg;
        };
        ap.name = "test_alt";
        alt_proto = RegisterProtocol(ap);
    });

    ClientSink sink;
    g_sink = &sink;
    EchoFixture fx;
    fx.server_m.add_protocol(alt_proto);
    ASSERT_TRUE(fx.Start());
    SocketUniquePtr cs;
    ASSERT_EQ(Socket::AddressSocket(fx.client_id, &cs), 0);

    // ALT frame first: the TST0 peek protocol must yield via TRY_OTHERS.
    {
        IOBuf out;
        out.append("ALT0", 4);
        const uint32_t len = 5;
        out.append((const char*)&len, 4);
        out.append("hello", 5);
        sink.pending.reset(1);
        ASSERT_EQ(cs->Write(&out), 0);
        ASSERT_EQ(sink.pending.wait(), 0);
    }
    // The socket is now sticky on ALT; a TST0 frame makes the ALT peek
    // path (none — ALT has no peek) fall back to TRY_OTHERS re-sniffing
    // into the TST0 parser.
    {
        IOBuf payload;
        payload.append("tst-after-alt");
        IOBuf framed;
        frame(&framed, payload);
        sink.pending.reset(1);
        ASSERT_EQ(cs->Write(&framed), 0);
        ASSERT_EQ(sink.pending.wait(), 0);
    }
    std::lock_guard<std::mutex> g(sink.mu);
    ASSERT_EQ(sink.responses.size(), 2u);
    EXPECT_EQ(sink.responses[0], "alt:hello");
    EXPECT_EQ(sink.responses[1], "tst-after-alt");
    g_sink = nullptr;
}

// Run-to-completion budget: a one-writev burst far past the inline
// budget completes fully (overflow falls back to the fiber fan-out) and
// both counters move.
TEST(Net, InlineDispatchBudgetOverflow) {
    ClientSink sink;
    g_sink = &sink;
    EchoFixture fx;
    ASSERT_TRUE(fx.Start());
    SocketUniquePtr cs;
    ASSERT_EQ(Socket::AddressSocket(fx.client_id, &cs), 0);

    const int64_t inlines_before = inline_dispatch::dispatches();
    const int64_t overflows_before = inline_dispatch::overflows();
    const int kN = 200;
    IOBuf burst;
    for (int i = 0; i < kN; ++i) {
        IOBuf payload;
        payload.append("burst-" + std::to_string(i));
        frame(&burst, payload);
    }
    const int32_t old_budget = FLAGS_inline_dispatch_budget.get();
    FLAGS_inline_dispatch_budget.set(2);
    sink.pending.reset(kN);
    const int write_rc = cs->Write(&burst);
    if (write_rc != 0) {
        // Nothing queued: waiting would hang. Restore and bail.
        FLAGS_inline_dispatch_budget.set(old_budget);
    }
    ASSERT_EQ(write_rc, 0);
    const int wait_rc = sink.pending.wait();
    // Restore BEFORE any assert can return out of the test — a leaked
    // budget of 2 would warp every later test's dispatch behavior.
    FLAGS_inline_dispatch_budget.set(old_budget);
    ASSERT_EQ(wait_rc, 0);
    {
        std::lock_guard<std::mutex> g(sink.mu);
        ASSERT_EQ(sink.responses.size(), (size_t)kN);
    }
    // The burst lands in few reads: some messages ran inline, and with
    // budget 2 the rest overflowed to the scheduler.
    EXPECT_GT(inline_dispatch::dispatches(), inlines_before);
    EXPECT_GT(inline_dispatch::overflows(), overflows_before);
    g_sink = nullptr;
}

// Cross-response write coalescing: responses the server queues during
// one dispatch round leave in a single writev — the accepted socket's
// biggest write batch spans several frames and the deferred-election
// counter moves (the rpc_socket_write_batch_bytes summary feeds off the
// same per-batch sizes).
TEST(Net, WriteCoalescingAcrossResponses) {
    ClientSink sink;
    g_sink = &sink;
    EchoFixture fx;
    ASSERT_TRUE(fx.Start());
    SocketUniquePtr cs;
    ASSERT_EQ(Socket::AddressSocket(fx.client_id, &cs), 0);

    const int64_t coalesced_before = SocketCoalescedWrites();
    const int kN = 100;
    const std::string body(100, 'c');
    IOBuf burst;
    for (int i = 0; i < kN; ++i) {
        IOBuf payload;
        payload.append(body);
        frame(&burst, payload);
    }
    sink.pending.reset(kN);
    ASSERT_EQ(cs->Write(&burst), 0);
    ASSERT_EQ(sink.pending.wait(), 0);
    EXPECT_GT(SocketCoalescedWrites(), coalesced_before);
    // The server's accepted connection wrote at least one batch of
    // multiple coalesced response frames (frame = 8 + 100 bytes).
    const std::vector<SocketId> conns = fx.acceptor.connections();
    ASSERT_EQ(conns.size(), 1u);
    SocketUniquePtr acc;
    ASSERT_EQ(Socket::AddressSocket(conns[0], &acc), 0);
    EXPECT_GE(acc->max_write_batch_bytes(), 2 * (int64_t)(8 + body.size()));
    g_sink = nullptr;
}

// Pooled-connection selection round-robins (FIFO) through the idle pool
// instead of convoying on the most recently returned socket.
TEST(Net, SocketPoolRoundRobins) {
    register_test_protocols();
    InputMessenger client_m({g_client_proto});
    EndPoint remote;
    str2endpoint("127.0.0.1:39999", &remote);  // never written to
    SocketPool* pool = SocketPool::singleton();
    SocketId a, b, c;
    ASSERT_EQ(pool->Get(remote, &client_m, &a), 0);
    ASSERT_EQ(pool->Get(remote, &client_m, &b), 0);
    ASSERT_EQ(pool->Get(remote, &client_m, &c), 0);
    EXPECT_EQ(pool->idle_count(remote), 0u);
    pool->Return(a);
    pool->Return(b);
    pool->Return(c);
    ASSERT_EQ(pool->idle_count(remote), 3u);
    SocketId r1, r2, r3;
    ASSERT_EQ(pool->Get(remote, &client_m, &r1), 0);
    ASSERT_EQ(pool->Get(remote, &client_m, &r2), 0);
    ASSERT_EQ(pool->Get(remote, &client_m, &r3), 0);
    // FIFO: the least recently returned member comes back first.
    EXPECT_EQ(r1, a);
    EXPECT_EQ(r2, b);
    EXPECT_EQ(r3, c);
    Socket::SetFailedById(a);
    Socket::SetFailedById(b);
    Socket::SetFailedById(c);
}

TEST(Net, ConnectFailureFailsSocket) {
    register_test_protocols();
    InputMessenger client_m({g_client_proto});
    // Port 1 on localhost: connection refused.
    EndPoint dead_ep;
    str2endpoint("127.0.0.1:1", &dead_ep);
    SocketOptions opts;
    opts.fd = -1;
    opts.remote_side = dead_ep;
    opts.on_edge_triggered_events = &InputMessenger::OnNewMessages;
    opts.user = &client_m;
    SocketId id;
    ASSERT_EQ(Socket::Create(opts, &id), 0);
    SocketUniquePtr s;
    ASSERT_EQ(Socket::AddressSocket(id, &s), 0);
    IOBuf data;
    data.append("doomed");
    EXPECT_EQ(s->Write(&data), 0);  // queued; fails async
    // The KeepWrite fiber discovers the refused connection and fails the
    // socket.
    for (int i = 0; i < 200 && !s->Failed(); ++i) {
        usleep(10000);
    }
    EXPECT_TRUE(s->Failed());
}

// ---------------- single-writer queue on a many-core host ----------------

namespace {

// A transport that takes every byte at once: the writer never parks, so
// it retires — and the next Write elects a new one — about once per
// request. Each request is one {thread, seq} record, and the transport
// is the single place they all pass through, so it can tell a lost or
// repeated request from its per-thread sequence and a second concurrent
// writer from re-entry.
class DrainTransport : public TransportEndpoint {
public:
    explicit DrainTransport(int nthreads)
        : next_seq((size_t)nthreads), efd_(eventfd(0, EFD_NONBLOCK)) {
        for (auto& n : next_seq) n.store(0, std::memory_order_relaxed);
    }
    ~DrainTransport() override { close(efd_); }
    int event_fd() const override { return efd_; }
    bool Established() const override { return true; }
    ssize_t CutFromIOBufList(IOBuf* const* pieces, size_t count,
                             int64_t*) override {
        if (inside_.exchange(true, std::memory_order_acq_rel)) {
            overlaps.fetch_add(1, std::memory_order_relaxed);
        }
        ssize_t n = 0;
        for (size_t i = 0; i < count; ++i) {
            uint32_t rec[2];
            while (pieces[i]->cutn(rec, sizeof(rec)) == sizeof(rec)) {
                n += (ssize_t)sizeof(rec);
                if (rec[0] >= next_seq.size() ||
                    rec[1] != next_seq[rec[0]].fetch_add(
                                  1, std::memory_order_acq_rel)) {
                    misordered.fetch_add(1, std::memory_order_relaxed);
                }
            }
        }
        inside_.store(false, std::memory_order_release);
        return n;
    }
    int WaitWritable(int64_t) override { return 0; }
    ssize_t Pump(IOPortal*, PumpStamps*) override {
        errno = EAGAIN;
        return -1;
    }
    void Close() override {}
    void Release() override { delete this; }  // owned by the socket

    // Records delivered so far per producing thread (= the next expected).
    std::vector<std::atomic<uint32_t>> next_seq;
    std::atomic<int64_t> misordered{0};
    std::atomic<int64_t> overlaps{0};

private:
    int efd_;
    std::atomic<bool> inside_{false};
};

}  // namespace

// Regression for the writer-retire race: the fetch_sub that brings
// write_pending_ to zero hands the writer role over, and the old code
// zeroed the shared consumed-count AFTER it — clobbering (or leaking a
// stale count into) a writer elected in that gap, so the queue wedged
// or elected two writers at once. One core never shows it; more threads
// than cores on a many-core host shows it within a second.
TEST(Net, ConcurrentWritersRetireExactlyOnce) {
    const int kThreads =
        std::max(8, (int)std::thread::hardware_concurrency() + 1);
    auto* transport = new DrainTransport(kThreads);
    SocketOptions opts;
    opts.fd = transport->event_fd();
    opts.transport = transport;
    opts.owns_transport = true;  // freed at recycle, after the last writer
    SocketId id;
    ASSERT_EQ(Socket::Create(opts, &id), 0);
    SocketUniquePtr s;
    ASSERT_EQ(Socket::AddressSocket(id, &s), 0);

    // Each thread keeps ONE request in flight: the queue runs empty all
    // the time, so writers retire and get elected at the highest rate.
    const int64_t deadline_us = monotonic_time_us() + 3 * 1000 * 1000;
    std::atomic<int64_t> written{0};
    std::atomic<int64_t> refused{0};
    std::atomic<int64_t> lost{0};
    std::atomic<int> finished{0};
    std::vector<std::thread> threads;
    for (int t = 0; t < kThreads; ++t) {
        threads.emplace_back([&, t] {
            for (uint32_t seq = 0; monotonic_time_us() < deadline_us; ++seq) {
                const uint32_t rec[2] = {(uint32_t)t, seq};
                IOBuf data;
                data.append(rec, sizeof(rec));
                if (s->Write(&data) != 0) {
                    refused.fetch_add(1, std::memory_order_relaxed);
                    break;
                }
                written.fetch_add(1, std::memory_order_relaxed);
                // A wedged queue never delivers: give up after 5s.
                const int64_t give_up = monotonic_time_us() + 5 * 1000 * 1000;
                while (transport->next_seq[(size_t)t].load(
                           std::memory_order_acquire) <= seq) {
                    if (monotonic_time_us() > give_up) {
                        lost.fetch_add(1, std::memory_order_relaxed);
                        finished.fetch_add(1);
                        return;
                    }
                    std::this_thread::yield();
                }
            }
            finished.fetch_add(1);
        });
    }
    // A writer that lost count spins inside Write forever; such a thread
    // cannot be joined, so a stuck run ends the process loudly instead.
    for (int i = 0; i < 2000 && finished.load() < kThreads; ++i) usleep(10000);
    if (finished.load() < kThreads) {
        fprintf(stderr,
                "ConcurrentWritersRetireExactlyOnce: %d of %d writers stuck "
                "inside Socket::Write (pending_writes=%lld)\n",
                kThreads - finished.load(), kThreads,
                (long long)s->pending_writes());
        abort();
    }
    for (auto& th : threads) th.join();
    int64_t delivered = 0;
    for (auto& n : transport->next_seq) delivered += n.load();
    EXPECT_EQ(0, refused.load());
    EXPECT_EQ(0, lost.load());
    EXPECT_EQ(0, s->pending_writes());
    EXPECT_EQ(0, s->unwritten_bytes());
    EXPECT_EQ(written.load(), delivered);
    EXPECT_EQ(0, transport->misordered.load());
    EXPECT_EQ(0, transport->overlaps.load());
    EXPECT_GT(written.load(), (int64_t)kThreads);
    s->SetFailed();
}

// ---------------- transport tier registry (ISSUE 12) ----------------

TEST(TransportTier, RegistryBuiltinsAndIdempotence) {
    // Built-ins exist with the capability story the descriptor seam
    // relies on: tcp moves bytes only; ici/shm_xproc are zero-copy and
    // descriptor-capable; device is the staging-ring tier.
    const int tcp = TierTcp();
    const int ici = TierIci();
    const int shm = TierShmXproc();
    const int dev = TierDevice();
    ASSERT_GE(tcp, 0);
    ASSERT_NE(tcp, ici);
    ASSERT_NE(ici, shm);
    ASSERT_NE(shm, dev);
    const TransportTier* t = GetTransportTier(tcp);
    ASSERT_TRUE(t != nullptr);
    EXPECT_FALSE(t->descriptor_capable);
    EXPECT_FALSE(t->zero_copy);
    EXPECT_TRUE(t->cross_process);
    t = GetTransportTier(ici);
    ASSERT_TRUE(t != nullptr);
    EXPECT_TRUE(t->descriptor_capable);
    EXPECT_TRUE(t->zero_copy);
    EXPECT_FALSE(t->cross_process);
    t = GetTransportTier(shm);
    ASSERT_TRUE(t != nullptr);
    EXPECT_TRUE(t->descriptor_capable);
    EXPECT_TRUE(t->cross_process);
    // Registration is idempotent by name (re-register returns the
    // existing id) and lookup by name round-trips.
    EXPECT_EQ(tcp, RegisterTransportTier({"tcp", true, true, false}));
    EXPECT_EQ(ici, FindTransportTier("ici"));
    EXPECT_EQ(-1, FindTransportTier("no_such_tier"));
    EXPECT_TRUE(GetTransportTier(-1) == nullptr);
    EXPECT_TRUE(GetTransportTier(10000) == nullptr);
    EXPECT_GE(TransportTierCount(), 4);
}

TEST(TransportTier, StatsAttributeByTier) {
    const int ici = TierIci();
    const int64_t in0 = transport_stats::in_bytes(ici);
    const int64_t stalls0 = transport_stats::credit_stalls(ici);
    transport_stats::AddIn(ici, 1234);
    transport_stats::AddCreditStall(ici);
    transport_stats::AddDescOut(ici, 99);
    EXPECT_EQ(in0 + 1234, transport_stats::in_bytes(ici));
    EXPECT_EQ(stalls0 + 1, transport_stats::credit_stalls(ici));
    EXPECT_GE(transport_stats::desc_out_bytes(ici), (int64_t)99);
    // Bad ids are ignored, never a crash.
    transport_stats::AddIn(-1, 5);
    transport_stats::AddIn(9999, 5);
    EXPECT_EQ((int64_t)0, transport_stats::in_bytes(9999));
    // The /pools section renders one line per tier.
    const std::string dump = transport_stats::DebugString();
    EXPECT_TRUE(dump.find("tier tcp") != std::string::npos);
    EXPECT_TRUE(dump.find("tier ici") != std::string::npos);
    EXPECT_TRUE(dump.find("tier shm_xproc") != std::string::npos);
    EXPECT_TRUE(dump.find("tier device") != std::string::npos);
}

TEST(TransportTier, DescriptorSeamGatesOnTierAndPool) {
    // Null socket: never capable, never in scope.
    EXPECT_FALSE(TransportDescriptorCapable(nullptr));
    EXPECT_FALSE(TransportDescriptorScopeOk(nullptr, 1));
    // A plain-fd socket is the tcp tier: bytes only, no descriptors —
    // regardless of what pool id a request names.
    int fds[2];
    ASSERT_EQ(0, socketpair(AF_UNIX, SOCK_STREAM, 0, fds));
    SocketOptions opts;
    opts.fd = fds[0];
    SocketId sid;
    ASSERT_EQ(0, Socket::Create(opts, &sid));
    SocketUniquePtr s;
    ASSERT_EQ(0, Socket::AddressSocket(sid, &s));
    EXPECT_EQ(TierTcp(), s->transport_tier());
    EXPECT_FALSE(TransportDescriptorCapable(s.get()));
    EXPECT_FALSE(TransportDescriptorScopeOk(s.get(), 42));
    s->SetFailedWithError(TERR_CLOSE);
    s.reset();
    close(fds[1]);
}

TEST(TransportTier, DcnTierRegisteredAndDescriptorIncapable) {
    // The cross-pod tier (ISSUE 14): a distinct registry entry — plain
    // byte stream, descriptor-INCAPABLE (the pod boundary shares no
    // pool mapping), cross-process. A socket forced onto it reports the
    // tier and fails both descriptor seams, so a pinned try degrades to
    // inline through the one seam.
    const int dcn = TierDcn();
    ASSERT_GE(dcn, 0);
    ASSERT_NE(dcn, TierTcp());
    const TransportTier* t = GetTransportTier(dcn);
    ASSERT_TRUE(t != nullptr);
    EXPECT_FALSE(t->descriptor_capable);
    EXPECT_FALSE(t->zero_copy);
    EXPECT_TRUE(t->cross_process);
    EXPECT_EQ(dcn, FindTransportTier("dcn"));
    EXPECT_TRUE(transport_stats::DebugString().find("tier dcn") !=
                std::string::npos);

    int fds[2];
    ASSERT_EQ(0, socketpair(AF_UNIX, SOCK_STREAM, 0, fds));
    SocketOptions opts;
    opts.fd = fds[0];
    opts.forced_transport_tier = dcn;
    SocketId sid;
    ASSERT_EQ(0, Socket::Create(opts, &sid));
    SocketUniquePtr s;
    ASSERT_EQ(0, Socket::AddressSocket(sid, &s));
    EXPECT_EQ(dcn, s->transport_tier());
    EXPECT_EQ(dcn, s->forced_transport_tier());
    EXPECT_FALSE(TransportDescriptorCapable(s.get()));
    EXPECT_FALSE(TransportDescriptorScopeOk(s.get(), 42));
    s->SetFailedWithError(TERR_CLOSE);
    s.reset();
    close(fds[1]);

    // Shaping arithmetic: latency + bytes/mbps, dcn-tier only.
    SetFlagValue("dcn_emu_latency_us", "500");
    SetFlagValue("dcn_emu_mbps", "100");
    EXPECT_TRUE(DcnShapingEnabled());
    EXPECT_EQ((int64_t)500 + 1000000 / 100,
              DcnShapeDelayUs(dcn, 1000000));
    // Inbound half: bandwidth only (latency is the writer's, once per
    // message — never per read burst).
    EXPECT_EQ((int64_t)1000000 / 100, DcnShapeReadDelayUs(dcn, 1000000));
    EXPECT_EQ((int64_t)0, DcnShapeDelayUs(TierTcp(), 1000000));
    EXPECT_EQ((int64_t)0, DcnShapeReadDelayUs(TierTcp(), 1000000));
    SetFlagValue("dcn_emu_latency_us", "0");
    SetFlagValue("dcn_emu_mbps", "0");
    EXPECT_FALSE(DcnShapingEnabled());
    EXPECT_EQ((int64_t)0, DcnShapeDelayUs(dcn, 1000000));
}

TEST(TransportTier, SocketMapKeyedByEndpointAndTier) {
    // (endpoint, tier) keying (ISSUE 14 satellite): a tcp and a dcn
    // "connection" to the SAME address are different sockets with
    // independent health state — a dcn failure never poisons the tcp
    // path, and each tier reconnects independently.
    InputMessenger m;
    EndPoint ep;
    str2endpoint("127.0.0.1:1", &ep);  // never connected (no write)
    SocketId tcp_id = INVALID_VREF_ID, dcn_id = INVALID_VREF_ID;
    ASSERT_EQ(0, SocketMap::singleton()->GetOrCreate(ep, &m, &tcp_id));
    ASSERT_EQ(0, SocketMap::singleton()->GetOrCreate(ep, &m, &dcn_id,
                                                     TierDcn()));
    EXPECT_NE(tcp_id, dcn_id);
    {
        SocketUniquePtr s;
        ASSERT_EQ(0, Socket::AddressSocket(dcn_id, &s));
        EXPECT_EQ(TierDcn(), s->transport_tier());
    }
    // Lookups are sticky per tier.
    SocketId again = INVALID_VREF_ID;
    ASSERT_EQ(0, SocketMap::singleton()->GetOrCreate(ep, &m, &again));
    EXPECT_EQ(tcp_id, again);
    ASSERT_EQ(0, SocketMap::singleton()->GetOrCreate(ep, &m, &again,
                                                     TierDcn()));
    EXPECT_EQ(dcn_id, again);
    // Failing the dcn socket replaces only the dcn entry; the tcp one
    // keeps its id (health state never shared across tiers).
    Socket::SetFailedById(dcn_id);
    SocketId fresh = INVALID_VREF_ID;
    ASSERT_EQ(0, SocketMap::singleton()->GetOrCreate(ep, &m, &fresh,
                                                     TierDcn()));
    EXPECT_NE(dcn_id, fresh);
    ASSERT_EQ(0, SocketMap::singleton()->GetOrCreate(ep, &m, &again));
    EXPECT_EQ(tcp_id, again);
    Socket::SetFailedById(tcp_id);
    Socket::SetFailedById(fresh);
    SocketMap::singleton()->Remove(ep, tcp_id);
    SocketMap::singleton()->Remove(ep, fresh, TierDcn());
}
