// Loopback echo benchmark through the FULL RPC stack: protobuf stub ->
// Channel -> tpu_std protocol -> Socket -> epoll -> Server -> service ->
// response, client and server in one process. Bulk bytes ride the
// attachment (zero-copy), matching the reference's echo benchmark setup
// (docs/cn/benchmark.md:104 — 2.3 GB/s large-payload echo on loopback;
// example/echo_c++ attachment echo).
//
// Prints one JSON line with --json:
//   {"mbps": ..., "qps_4k": ..., "p50_us_4k": ..., "p99_us_4k": ...}
#include <signal.h>
#include <sys/prctl.h>
#include <sys/wait.h>
#include <unistd.h>

#include <atomic>
#include <map>
#include <cstdio>
#include <cstring>
#include <string>

#include "bench_echo.pb.h"
#include "tbase/cpu_profiler.h"
#include "tbase/crc32c.h"
#include "tbase/errno.h"
#include "tbase/fast_rand.h"
#include "tbase/flags.h"
#include "tbase/time.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tici/ici_link.h"
#include "tici/shm_link.h"
#include "tnet/socket.h"
#include "tfiber/fiber_sync.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "trpc/redis.h"
#include "trpc/server.h"
#include "tvar/latency_recorder.h"
#include "tvar/stage_recorder.h"
#include "tvar/variable.h"

using namespace tpurpc;

DECLARE_int32(socket_send_buffer_size);
DECLARE_int32(socket_recv_buffer_size);

// Long-tail injection for the backup-request benchmark (reference
// docs/cn/benchmark.md:126-206: 1% of requests made slow, latency CDF
// with/without backup requests stays flat).
DEFINE_int32(echo_slow_percent, 0, "percent of echo calls made slow");
DEFINE_int32(echo_slow_us, 10000, "injected handler delay in us");

namespace {

class EchoServiceImpl : public benchpb::EchoService {
public:
    void Echo(google::protobuf::RpcController* cntl_base,
              const benchpb::EchoRequest* request,
              benchpb::EchoResponse* response,
              google::protobuf::Closure* done) override {
        Controller* cntl = static_cast<Controller*>(cntl_base);
        const int slow_pct = FLAGS_echo_slow_percent.get();
        if (slow_pct > 0 && (int)(fast_rand() % 100) < slow_pct) {
            fiber_usleep(FLAGS_echo_slow_us.get());
        }
        response->set_send_ts_us(request->send_ts_us());
        if (request->has_payload()) {
            response->set_payload(request->payload());
        }
        // One-sided pool attachment (ISSUE 9): the bytes were never
        // copied — read them IN PLACE from the mapped sender pool and
        // answer with their checksum + placement evidence, duplicating
        // nothing. (Echoing them back as response bytes would undo the
        // zero-copy the descriptor bought.)
        const Controller::PoolAttachment& pa =
            cntl->request_pool_attachment();
        if (pa.data != nullptr) {
            // inline = attachment bytes that crossed the wire alongside
            // the descriptor (0 proves the payload rode as a reference).
            char verdict[96];
            snprintf(verdict, sizeof(verdict),
                     "crc32c=%08x len=%llu inline=%zu",
                     crc32c_extend(0, pa.data, pa.length),
                     (unsigned long long)pa.length,
                     cntl->request_attachment().size());
            response->set_payload(verdict);
        }
        // Response-direction descriptor (ISSUE 12): a "desc_rsp:N:S"
        // request asks for N bytes answered as a pool-block REFERENCE —
        // the handler fills a slab slot in its OWN pool (pattern seeded
        // by S: byte 0 = S, the rest 'a'+S%26) and pins it; the client
        // resolves it against its handshake-made mapping of this pool
        // with zero inline payload bytes.
        unsigned long long rsp_n = 0;
        unsigned rsp_seed = 0;
        if (sscanf(request->payload().c_str(), "desc_rsp:%llu:%u", &rsp_n,
                   &rsp_seed) == 2 &&
            rsp_n > 0) {
            IOBuf out;
            char* data = nullptr;
            if (IciBlockPool::AllocatePoolAttachment((size_t)rsp_n, &out,
                                                     &data)) {
                memset(data, 'a' + (int)(rsp_seed % 26), (size_t)rsp_n);
                data[0] = (char)rsp_seed;
                cntl->set_response_pool_attachment(std::move(out));
                response->set_payload("desc_rsp_ok");
            } else {
                cntl->SetFailed(TERR_RESPONSE,
                                "pool attachment alloc failed");
            }
        }
        cntl->response_attachment().append(cntl->request_attachment());
        done->Run();
    }
};

struct CallCtx {
    Controller cntl;
    benchpb::EchoRequest req;
    benchpb::EchoResponse res;
    CountdownEvent* pending;
    LatencyRecorder* lat;
    std::atomic<int64_t>* bytes;
};

// Every mode runs over loopback with 10s timeouts: one failed call means
// the path under test broke, and a rate computed around it would read as
// a (slow) success. Counted here, checked before any result line.
std::atomic<int64_t> g_failed_calls{0};

bool AnyCallFailed() {
    const int64_t n = g_failed_calls.load(std::memory_order_relaxed);
    if (n == 0) return false;
    fprintf(stderr, "%lld rpc(s) failed; no result\n", (long long)n);
    return true;
}

void OnEchoDone(CallCtx* ctx) {
    if (!ctx->cntl.Failed()) {
        if (ctx->lat != nullptr) {
            *ctx->lat << (monotonic_time_us() - ctx->res.send_ts_us());
        }
        if (ctx->bytes != nullptr) {
            ctx->bytes->fetch_add(
                (int64_t)ctx->cntl.response_attachment().size(),
                std::memory_order_relaxed);
        }
    } else {
        fprintf(stderr, "rpc failed: %s\n", ctx->cntl.ErrorText().c_str());
        g_failed_calls.fetch_add(1, std::memory_order_relaxed);
    }
    ctx->pending->signal();
    delete ctx;
}

// `iters` async echo RPCs with `window` in flight; returns elapsed secs.
// backup_ms >= 0 arms a backup request per call at that delay.
double run_round(benchpb::EchoService_Stub& stub, size_t attachment_bytes,
                 int iters, int window, LatencyRecorder* lat,
                 std::atomic<int64_t>* bytes, int64_t backup_ms = -1) {
    // Pre-built attachment appended by reference (zero-copy), matching the
    // reference drivers (example/multi_threaded_echo_c++ appends a global
    // butil::IOBuf g_attachment).
    IOBuf filler;
    filler.append(std::string(attachment_bytes, 'e'));
    Timer t;
    t.start();
    int sent = 0;
    CountdownEvent pending(0);
    while (sent < iters) {
        const int batch = std::min(window, iters - sent);
        pending.reset(batch);
        for (int i = 0; i < batch; ++i) {
            auto* ctx = new CallCtx;
            ctx->pending = &pending;
            ctx->lat = lat;
            ctx->bytes = bytes;
            ctx->cntl.set_timeout_ms(10000);
            if (backup_ms >= 0) {
                ctx->cntl.set_backup_request_ms(backup_ms);
                ctx->cntl.set_max_retry(1);  // backup consumes retry budget
            }
            ctx->req.set_send_ts_us(monotonic_time_us());
            if (attachment_bytes > 0) {
                ctx->cntl.request_attachment().append(filler);
            }
            stub.Echo(&ctx->cntl, &ctx->req, &ctx->res,
                      google::protobuf::NewCallback(OnEchoDone, ctx));
        }
        if (pending.wait() != 0) return -1;
        sent += batch;
    }
    t.stop();
    return (double)t.n_elapsed() / 1e9;
}

// One-sided pool-descriptor round (ISSUE 9): attachments cross the
// ici/shm seam as (pool_id, offset, len, crc) references; the server
// reads them in place and answers with the checksum it computed there.
// Returns logical MB/s, or -1 on any verification failure.
double run_pool_desc_round(benchpb::EchoService_Stub& stub,
                           size_t attachment_bytes, int iters,
                           int* zero_copy_ok) {
    *zero_copy_ok = 1;
    Timer t;
    t.start();
    for (int i = 0; i < iters; ++i) {
        IOBuf att;
        char* data = nullptr;
        if (!IciBlockPool::AllocatePoolAttachment(attachment_bytes, &att,
                                                  &data)) {
            fprintf(stderr, "pool attachment alloc failed\n");
            return -1;
        }
        // Distinct pattern per call so a stale mapping can't pass crc.
        memset(data, 'a' + (i % 26), attachment_bytes);
        data[0] = (char)i;
        const uint32_t crc =
            crc32c_extend(0, data, attachment_bytes);
        Controller cntl;
        cntl.set_timeout_ms(10000);
        cntl.set_request_pool_attachment(std::move(att));
        benchpb::EchoRequest req;
        benchpb::EchoResponse res;
        req.set_send_ts_us(monotonic_time_us());
        stub.Echo(&cntl, &req, &res, nullptr);
        if (cntl.Failed()) {
            fprintf(stderr, "pool-desc rpc failed: %s\n",
                    cntl.ErrorText().c_str());
            return -1;
        }
        char expect[96];
        snprintf(expect, sizeof(expect), "crc32c=%08x len=%llu inline=0",
                 crc, (unsigned long long)attachment_bytes);
        if (res.payload() != expect) {
            fprintf(stderr, "pool-desc verdict mismatch: got '%s' want "
                            "'%s'\n",
                    res.payload().c_str(), expect);
            *zero_copy_ok = 0;
            return -1;
        }
    }
    t.stop();
    const double secs = (double)t.n_elapsed() / 1e9;
    return (double)attachment_bytes * iters / (1024.0 * 1024.0) / secs;
}

// Response-direction descriptor round (ISSUE 12): a tiny request asks
// the server to answer `rsp_bytes` as a pool-block reference; the
// client's resolve path crc-verifies the in-place view against the
// descriptor (the wire contract), and this round additionally
// spot-checks the server's seeded pattern and that ZERO payload bytes
// arrived inline. Returns logical MB/s, or -1 on verification failure.
// Each iteration's controller teardown sends the desc_ack that unpins
// the server's block — the pinned_after gauge proves the cycle.
double run_pool_desc_rsp_round(benchpb::EchoService_Stub& stub,
                               size_t rsp_bytes, int iters,
                               int* zero_copy_ok) {
    *zero_copy_ok = 1;
    Timer t;
    t.start();
    for (int i = 0; i < iters; ++i) {
        Controller cntl;
        cntl.set_timeout_ms(10000);
        benchpb::EchoRequest req;
        benchpb::EchoResponse res;
        char ask[64];
        snprintf(ask, sizeof(ask), "desc_rsp:%zu:%u", rsp_bytes,
                 (unsigned)i);
        req.set_payload(ask);
        req.set_send_ts_us(monotonic_time_us());
        stub.Echo(&cntl, &req, &res, nullptr);
        if (cntl.Failed()) {
            fprintf(stderr, "pool-desc rsp rpc failed: %s\n",
                    cntl.ErrorText().c_str());
            return -1;
        }
        const Controller::PoolAttachment& view =
            cntl.response_pool_attachment();
        if (view.data == nullptr || view.length != rsp_bytes ||
            cntl.response_attachment().size() != 0 ||
            view.data[0] != (char)i ||
            view.data[1] != (char)('a' + i % 26)) {
            fprintf(stderr,
                    "pool-desc rsp verdict mismatch: view=%p len=%llu "
                    "inline=%zu\n",
                    (const void*)view.data,
                    (unsigned long long)view.length,
                    cntl.response_attachment().size());
            *zero_copy_ok = 0;
            return -1;
        }
        // Controller goes out of scope here: the view release acks the
        // server's pin.
    }
    t.stop();
    const double secs = (double)t.n_elapsed() / 1e9;
    return (double)rsp_bytes * iters / (1024.0 * 1024.0) / secs;
}

// qps-vs-caller-fibers scaling sweep (reference docs/cn/benchmark.md:110
// qps_vs_threadnum): N fibers issue SYNC 4KB echoes back-to-back for a
// fixed wall-time slice; near-linear growth to 16 callers is the bar.
struct ScaleCtx {
    benchpb::EchoService_Stub* stub;
    LatencyRecorder* lat;
    std::atomic<bool>* stop;
    std::atomic<int64_t>* calls;
    std::atomic<int64_t>* caller_us;  // sum of the callers' own latencies
    IOBuf* filler;
};

void* ScaleCaller(void* arg) {
    auto* c = (ScaleCtx*)arg;
    while (!c->stop->load(std::memory_order_relaxed)) {
        Controller cntl;
        cntl.set_timeout_ms(10000);
        benchpb::EchoRequest req;
        benchpb::EchoResponse res;
        req.set_send_ts_us(monotonic_time_us());
        cntl.request_attachment().append(*c->filler);
        c->stub->Echo(&cntl, &req, &res, nullptr);
        if (!cntl.Failed()) {
            const int64_t us = monotonic_time_us() - res.send_ts_us();
            *c->lat << us;
            c->caller_us->fetch_add(us, std::memory_order_relaxed);
            c->calls->fetch_add(1, std::memory_order_relaxed);
        } else {
            g_failed_calls.fetch_add(1, std::memory_order_relaxed);
        }
    }
    return nullptr;
}

// One stderr line at each edge of a sweep level: this (client) process's
// stage-clock table and lost-wake-up counters, cumulative, so that
// tools/stage_closure.py can difference a level and set the stages'
// sum beside the callers' own mean (calls, caller_sum_us). stdout keeps
// its one result line.
void PrintStageMark(const char* edge, int callers, int64_t calls,
                    int64_t caller_sum_us) {
    std::string rescued = "0";
    Variable::describe_exposed("rpc_scheduler_park_timeouts_found_work",
                               &rescued);
    fprintf(stderr,
            "STAGES %s callers=%d calls=%lld caller_sum_us=%lld "
            "park_timeouts_found_work=%s stages=%s\n",
            edge, callers, (long long)calls, (long long)caller_sum_us,
            rescued.c_str(), stage::DumpJson().c_str());
    fflush(stderr);
}

// Runs one sweep level; returns qps and fills *p99_us.
double RunScaleLevel(benchpb::EchoService_Stub& stub, int ncallers,
                     int duration_ms, long long* p99_us) {
    IOBuf filler;
    filler.append(std::string(4096, 'e'));
    LatencyRecorder lat;
    std::atomic<bool> stop{false};
    std::atomic<int64_t> calls{0};
    std::atomic<int64_t> caller_us{0};
    ScaleCtx ctx{&stub, &lat, &stop, &calls, &caller_us, &filler};
    std::vector<fiber_t> tids((size_t)ncallers);
    PrintStageMark("begin", ncallers, 0, 0);
    const int64_t t0 = monotonic_time_us();
    for (auto& tid : tids) {
        fiber_start_background(&tid, nullptr, ScaleCaller, &ctx);
    }
    usleep(duration_ms * 1000);
    stop.store(true, std::memory_order_relaxed);
    for (auto tid : tids) fiber_join(tid, nullptr);
    const double secs = (double)(monotonic_time_us() - t0) / 1e6;
    PrintStageMark("end", ncallers, calls.load(), caller_us.load());
    *p99_us = (long long)lat.latency_percentile(0.99);
    return (double)calls.load() / secs;
}

// Child mode for the cross-process benchmark/tests: a standalone echo
// server with the ICI handshake enabled, port announced on stdout.
// Exits when stdin reaches EOF (parent closed its pipe or died).
const char* g_tls_cert = nullptr;
const char* g_tls_key = nullptr;

int RunIciServer() {
    prctl(PR_SET_PDEATHSIG, SIGKILL);  // die with the parent
    FLAGS_socket_send_buffer_size.set(1 << 20);
    FLAGS_socket_recv_buffer_size.set(1 << 20);
    if (IciBlockPool::Init() != 0) return 1;
    static EchoServiceImpl service;
    static Server server;
    if (server.AddService(&service) != 0) return 1;
    // Echo never blocks in server mode (no tail injection here):
    // run-to-completion dispatch is safe.
    server.SetMethodInlineSafe("benchpb.EchoService", "Echo");
    static RedisService redis;
    redis.AddBasicKvCommands();
    server.set_redis_service(&redis);
    ServerOptions sopts;
    if (g_tls_cert != nullptr && g_tls_key != nullptr) {
        sopts.tls_cert_path = g_tls_cert;
        sopts.tls_key_path = g_tls_key;
    }
    EndPoint listen;
    str2endpoint("127.0.0.1:0", &listen);
    if (server.Start(listen, &sopts) != 0) return 1;
    printf("PORT %d\n", server.listened_port());
    fflush(stdout);
    char buf[16];
    while (read(0, buf, sizeof(buf)) > 0) {
    }
    // Orderly stop, then _exit: running static destructors in a process
    // whose dispatcher/timer/sampler/worker threads are still live races
    // frees against those threads (observed as an exit-time UAF under
    // ASan). Long-lived server processes skip static teardown by design;
    // Stop+Join is the real shutdown.
    server.Stop();
    server.Join();
    fflush(nullptr);
    _exit(0);
}

// Spawn this binary as --ici-server; returns the child's pid and fills
// *port. *stdin_wr keeps the child alive: closing it shuts the child down.
pid_t SpawnIciServer(int* port, int* stdin_wr) {
    int out_pipe[2], in_pipe[2];
    if (pipe(out_pipe) != 0 || pipe(in_pipe) != 0) return -1;
    const pid_t pid = fork();
    if (pid < 0) return -1;
    if (pid == 0) {
        dup2(out_pipe[1], 1);
        dup2(in_pipe[0], 0);
        close(out_pipe[0]);
        close(out_pipe[1]);
        close(in_pipe[0]);
        close(in_pipe[1]);
        execl("/proc/self/exe", "echo_bench", "--ici-server",
              (char*)nullptr);
        _exit(127);
    }
    close(out_pipe[1]);
    close(in_pipe[0]);
    *stdin_wr = in_pipe[1];
    // Read "PORT <n>\n" from the child.
    char line[64];
    size_t got = 0;
    while (got < sizeof(line) - 1) {
        const ssize_t r = read(out_pipe[0], line + got, 1);
        if (r <= 0) break;
        if (line[got] == '\n') break;
        ++got;
    }
    line[got] = '\0';
    close(out_pipe[0]);
    if (sscanf(line, "PORT %d", port) != 1) {
        kill(pid, SIGKILL);
        waitpid(pid, nullptr, 0);
        return -1;
    }
    return pid;
}

}  // namespace

int main(int argc, char** argv) {
    bool json = false;
    bool use_ici = false;
    bool xproc = false;
    bool tail = false;
    bool scale = false;
    bool pooled = false;
    bool pool_desc = false;
    const char* prof_path = nullptr;
    bool ici_server = false;
    for (int i = 1; i < argc; ++i) {
        if (strcmp(argv[i], "--json") == 0) json = true;
        if (strcmp(argv[i], "--ici") == 0) use_ici = true;
        if (strcmp(argv[i], "--xproc") == 0) xproc = true;
        if (strcmp(argv[i], "--tail") == 0) tail = true;
        if (strcmp(argv[i], "--scale") == 0) scale = true;
        if (strcmp(argv[i], "--pooled") == 0) pooled = true;
        // Canonical spelling: --pool_desc (matches rpc_press and every
        // other underscore flag); the historical --pool-desc is still
        // accepted.
        if (strcmp(argv[i], "--pool_desc") == 0 ||
            strcmp(argv[i], "--pool-desc") == 0) {
            pool_desc = true;
        }
        if (strcmp(argv[i], "--ici-server") == 0) ici_server = true;
        if (strcmp(argv[i], "--help") == 0 || strcmp(argv[i], "-h") == 0) {
            printf(
                "usage: echo_bench [--json] [--ici | --xproc] [--tail] "
                "[--scale] [--pooled]\n"
                "                  [--pool_desc] [--prof FILE] "
                "[--tls-cert F --tls-key F]\n"
                "  --pool_desc   one-sided descriptor rounds, BOTH "
                "directions (requires\n"
                "                --ici or --xproc). Canonical spelling; "
                "--pool-desc is an\n"
                "                accepted alias.\n");
            return 0;
        }
        if (strcmp(argv[i], "--tls-cert") == 0 && i + 1 < argc) {
            g_tls_cert = argv[++i];
        }
        if (strcmp(argv[i], "--tls-key") == 0 && i + 1 < argc) {
            g_tls_key = argv[++i];
        }
        if (strcmp(argv[i], "--prof") == 0 && i + 1 < argc) {
            prof_path = argv[++i];
        }
    }
    if (ici_server) return RunIciServer();
    // Spawn the cross-process server BEFORE any framework threads exist
    // (fork after the dispatcher/fiber workers start is unsafe).
    int xproc_port = 0;
    int xproc_stdin = -1;
    pid_t xproc_pid = -1;
    if (xproc) {
        xproc_pid = SpawnIciServer(&xproc_port, &xproc_stdin);
        if (xproc_pid < 0) {
            fprintf(stderr, "failed to spawn --ici-server child\n");
            return 1;
        }
        // The child's portal, for whoever wants its /status beside this
        // process's STAGES lines (tools/stage_closure.py).
        fprintf(stderr, "XPROC_SERVER_PORT %d\n", xproc_port);
    }
    // Windowed 1MB messages benefit from fixed large socket buffers on
    // loopback; production connections keep kernel autotuning (-1).
    FLAGS_socket_send_buffer_size.set(1 << 20);
    FLAGS_socket_recv_buffer_size.set(1 << 20);
    EchoServiceImpl service;
    Server server;
    if (server.AddService(&service) != 0) return 1;

    Channel channel;
    ChannelOptions copts;
    copts.timeout_ms = 10000;
    // Pooled mode: one in-flight RPC per connection (the reference's
    // multi-connection headline configuration, docs/cn/benchmark.md:104).
    if (pooled) copts.connection_type = CONNECTION_TYPE_POOLED;
    if (xproc) {
        // Cross-process data plane: TCP handshake to the child, then the
        // shared-memory queue pair (tici/shm_link.h). The server runs in
        // its own process; TCP stays as doorbell + failure detector.
        if (IciBlockPool::Init() != 0) return 1;
        EndPoint ep;
        str2endpoint("127.0.0.1", xproc_port, &ep);
        if (channel.InitIci(ep, &copts) != 0) return 1;
    } else if (use_ici) {
        // ICI data plane: registered-memory pool + software queue pair
        // (the loopback stand-in for the interconnect; see
        // cpp/tici/ici_link.h). One copy per byte instead of TCP's four.
        if (IciBlockPool::Init() != 0) return 1;
        if (server.StartNoListen(nullptr) != 0) return 1;
        IciLink& link = *IciLink::Create();
        SocketOptions sopts;
        sopts.fd = link.second()->event_fd();
        sopts.transport = link.second();
        sopts.owns_transport = true;
        sopts.on_edge_triggered_events = InputMessenger::OnNewMessages;
        sopts.user = server.messenger();
        SocketId server_sid;
        if (Socket::Create(sopts, &server_sid) != 0) return 1;
        SocketOptions ccopts;
        ccopts.fd = link.first()->event_fd();
        ccopts.transport = link.first();
        ccopts.owns_transport = true;
        ccopts.on_edge_triggered_events = InputMessenger::OnNewMessages;
        ccopts.user = Channel::client_messenger();
        SocketId client_sid;
        if (Socket::Create(ccopts, &client_sid) != 0) return 1;
        if (channel.InitWithSocketId(client_sid, &copts) != 0) return 1;
    } else {
        EndPoint listen;
        str2endpoint("127.0.0.1:0", &listen);
        if (server.Start(listen, nullptr) != 0) return 1;
        EndPoint ep;
        str2endpoint("127.0.0.1", server.listened_port(), &ep);
        if (channel.Init(ep, &copts) != 0) return 1;
    }
    benchpb::EchoService_Stub stub(&channel);

    // Run-to-completion (ISSUE 7): the echo handler is cheap and
    // non-blocking, so flag it inline-safe — small requests run on the
    // input fiber and their responses coalesce into one writev per
    // burst. NOT in tail mode: there the handler sleeps (the injected
    // long tail), which would head-of-line-block the connection and
    // defeat the backup request riding the same socket.
    if (!tail) {
        server.SetMethodInlineSafe("benchpb.EchoService", "Echo");
    }

    if (pool_desc) {
        // One-sided descriptor rounds, BOTH directions (ISSUE 12):
        // requires a pool-mapped link (--ici in-process loopback or
        // --xproc shm link) — the Transport seam degrades plain-TCP
        // tries to inline instead, which is exactly what this round must
        // NOT measure.
        if (!use_ici && !xproc) {
            fprintf(stderr, "--pool_desc requires --ici or --xproc\n");
            return 1;
        }
        // 1MB-class slot minus the block header: the largest payload a
        // single slab-class block carries without spilling a class up.
        const size_t kDescBytes = (1u << 20) - 128;
        int zero_copy_ok = 0;
        run_pool_desc_round(stub, kDescBytes, 20, &zero_copy_ok);  // warm
        const int kIters = 200;
        const double mbps =
            run_pool_desc_round(stub, kDescBytes, kIters, &zero_copy_ok);
        if (mbps < 0) return 1;
        // Response direction: the server answers with references into
        // ITS pool; the client resolves them against the
        // handshake-mapped peer pool with zero inline payload bytes.
        int rsp_zero_copy_ok = 0;
        run_pool_desc_rsp_round(stub, kDescBytes, 20,
                                &rsp_zero_copy_ok);  // warm
        const double rsp_mbps = run_pool_desc_rsp_round(
            stub, kDescBytes, kIters, &rsp_zero_copy_ok);
        if (rsp_mbps < 0) return 1;
        // Leak gauge (ISSUE 10 satellite): after the rounds every pinned
        // block must be back in the pool — a nonzero pinned_after in
        // the result line is the descriptor path leaking under load. The
        // LAST response ack may still be in flight (it rides the wire
        // after the RPC completes): give it a bounded moment.
        long long pinned_after = (long long)block_lease::pinned();
        for (int w = 0; w < 100 && pinned_after != 0; ++w) {
            usleep(20 * 1000);
            pinned_after = (long long)block_lease::pinned();
        }
        const long long reaped = (long long)(
            block_lease::expired_reaped() + block_lease::peer_released());
        if (json) {
            printf("{\"pool_desc_mbps\": %.1f, \"pool_desc_calls\": %d, "
                   "\"pool_desc_bytes\": %zu, \"pool_desc_zero_copy\": "
                   "%d, \"pool_desc_rsp_mbps\": %.1f, "
                   "\"pool_desc_rsp_calls\": %d, "
                   "\"pool_desc_rsp_zero_copy\": %d, "
                   "\"pool_desc_rsp_inline_bytes\": 0, "
                   "\"pool_desc_pinned_after\": %lld, "
                   "\"pool_desc_reaped\": %lld}\n",
                   mbps, kIters, kDescBytes, zero_copy_ok, rsp_mbps,
                   kIters, rsp_zero_copy_ok, pinned_after, reaped);
        } else {
            printf("pool-descriptor echo: req %.1f MB/s, rsp %.1f MB/s "
                   "logical (%d calls x %zu bytes each way, zero-copy "
                   "req %s rsp %s, pinned-after %lld, reaped %lld)\n",
                   mbps, rsp_mbps, kIters, kDescBytes,
                   zero_copy_ok ? "verified" : "FAILED",
                   rsp_zero_copy_ok ? "verified" : "FAILED", pinned_after,
                   reaped);
        }
        if (xproc_pid > 0) {
            close(xproc_stdin);
            int status = 0;
            waitpid(xproc_pid, &status, 0);
        }
        return zero_copy_ok && rsp_zero_copy_ok ? 0 : 1;
    }

    if (tail) {
        // Backup-request tail benchmark (reference benchmark.md:126-206):
        // 2% of handler calls sleep echo_slow_us; compare the latency
        // distribution without and with backup requests armed at 2ms.
        run_round(stub, 4096, 500, 16, nullptr, nullptr);  // warmup
        FLAGS_echo_slow_percent.set(2);
        const int kTailIters = 6000;
        LatencyRecorder lat_nb, lat_b;
        lat_nb.expose("tail_echo_nobackup");
        lat_b.expose("tail_echo_backup");
        if (run_round(stub, 4096, kTailIters, 16, &lat_nb, nullptr) < 0) {
            return 1;
        }
        if (run_round(stub, 4096, kTailIters, 16, &lat_b, nullptr, 2) < 0) {
            return 1;
        }
        FLAGS_echo_slow_percent.set(0);
        if (AnyCallFailed()) return 1;
        if (json) {
            printf("{\"tail_p50_us\": %lld, "
                   "\"tail_p99_nobackup_us\": %lld, "
                   "\"tail_p999_nobackup_us\": %lld, "
                   "\"tail_p99_backup_us\": %lld, "
                   "\"tail_p999_backup_us\": %lld}\n",
                   (long long)lat_b.latency_percentile(0.5),
                   (long long)lat_nb.latency_percentile(0.99),
                   (long long)lat_nb.latency_percentile(0.999),
                   (long long)lat_b.latency_percentile(0.99),
                   (long long)lat_b.latency_percentile(0.999));
        } else {
            printf("tail (2%% of calls +%dus), no backup: p50 %lld p99 "
                   "%lld p999 %lld\n",
                   FLAGS_echo_slow_us.get(),
                   (long long)lat_nb.latency_percentile(0.5),
                   (long long)lat_nb.latency_percentile(0.99),
                   (long long)lat_nb.latency_percentile(0.999));
            printf("tail with backup@2ms:          p50 %lld p99 %lld "
                   "p999 %lld\n",
                   (long long)lat_b.latency_percentile(0.5),
                   (long long)lat_b.latency_percentile(0.99),
                   (long long)lat_b.latency_percentile(0.999));
        }
        return 0;
    }

    if (scale) {
        // qps vs caller fibers (reference benchmark.md:110-124).
        run_round(stub, 4096, 500, 16, nullptr, nullptr);  // warmup
        const int levels[] = {1, 4, 16, 64};
        double qps[4];
        long long p99[4];
        for (int i = 0; i < 4; ++i) {
            qps[i] = RunScaleLevel(stub, levels[i], 1500, &p99[i]);
        }
        if (AnyCallFailed()) return 1;
        if (json) {
            printf("{\"scale_qps_1\": %.0f, \"scale_qps_4\": %.0f, "
                   "\"scale_qps_16\": %.0f, \"scale_qps_64\": %.0f, "
                   "\"scale_p99_us_1\": %lld, \"scale_p99_us_4\": %lld, "
                   "\"scale_p99_us_16\": %lld, \"scale_p99_us_64\": "
                   "%lld}\n",
                   qps[0], qps[1], qps[2], qps[3], p99[0], p99[1], p99[2],
                   p99[3]);
        } else {
            for (int i = 0; i < 4; ++i) {
                printf("callers %2d: %8.0f qps  p99 %lldus\n", levels[i],
                       qps[i], p99[i]);
            }
        }
        return 0;
    }

    LatencyRecorder lat;
    lat.expose("rpc_echo_4k_latency");

    // Warmup.
    run_round(stub, 4096, 500, 32, nullptr, nullptr);
    if (prof_path != nullptr) StartCpuProfiler();

    // 4KB round.
    const int kSmallIters = 20000;
    const double small_secs =
        run_round(stub, 4096, kSmallIters, 64, &lat, nullptr);
    if (small_secs < 0) return 1;
    const double qps_4k = kSmallIters / small_secs;
    const long long p50 = (long long)lat.latency_percentile(0.5);
    const long long p99 = (long long)lat.latency_percentile(0.99);

    // 1MB round.
    std::atomic<int64_t> bytes{0};
    const int kBigIters = 300;
    const double big_secs =
        run_round(stub, 1 << 20, kBigIters, 4, nullptr, &bytes);
    if (big_secs < 0) return 1;
    const double mbps = (double)bytes.load() / (1024.0 * 1024.0) / big_secs;
    if (prof_path != nullptr) {
        const int n = StopCpuProfiler(prof_path);
        fprintf(stderr, "wrote %d samples to %s\n", n, prof_path);
    }

    if (AnyCallFailed()) return 1;
    if (json) {
        printf("{\"mbps\": %.1f, \"qps_4k\": %.0f, \"p50_us_4k\": %lld, "
               "\"p99_us_4k\": %lld}\n",
               mbps, qps_4k, p50, p99);
    } else {
        printf("RPC 1MB attachment echo: %.1f MB/s (%d calls)\n", mbps,
               kBigIters);
        printf("RPC 4KB echo: %.0f qps, p50 %lldus, p99 %lldus\n", qps_4k,
               p50, p99);
    }
    if (xproc_pid > 0) {
        close(xproc_stdin);  // child sees stdin EOF and exits
        int status = 0;
        waitpid(xproc_pid, &status, 0);
    }
    return 0;
}
