// rpc_press: target-QPS load generator (reference tools/rpc_press — we
// drive the echo fixture service rather than dynamically-loaded protos;
// the token-bucket pacing and latency reporting match the reference's
// rdma_performance client.cpp:50-68).
//
//   rpc_press --server=ip:port [--qps=10000] [--duration_s=10]
//             [--payload=4096 | --body_bytes=4096] [--callers=8]
//             [--press_threads=1] [--pooled] [--pool_desc]
//             [--timeout_ms=5000] [--metrics_csv=path] [--tenant=name]
//             [--priority=0..7]
//             [--tenants=a:8,b:1 | a:8:7,b:1:1 | a:8:7:128,b:1:1:65536]
//             [--via=ip:port] [--sessions=N]
//
// --via=ROUTER_ADDR (ISSUE 16): drive the load THROUGH a tpu_router
// front door instead of a backend directly. At the end the tool scrapes
// the router's /router?format=json and reports the ROUTER-ADDED latency
// — the client-observed p99 minus the router's backend-measured p99 —
// plus the router's hedge count (text + `press_via_p99_us` /
// `press_hedges` in --json). --sessions=N gives the FIRST N callers a
// sticky session id each ("s0".."s<N-1>", stamped on every request) so
// one run exercises the router's pinned path AND — from the remaining
// sessionless callers — its hedged path.
//
// --pool_desc (ISSUE 10 satellite, mirrors echo_bench --pool-desc):
// connect over the shm-ICI link (IciBlockPool + Channel::InitIci) and
// send every payload as a one-sided (pool_id, offset, len, crc, epoch)
// descriptor pinned under a block lease — descriptor traffic at target
// QPS, for pool/lease/epoch soaks and bench rounds. Responses carrying
// TERR_STALE_EPOCH are counted separately (press_stale_epoch): under
// chaos_pool stale injection they are EXPECTED retriable failures, not
// generator errors.
//
// --press_threads=N drives N independent pinned channels (one connection
// each, callers spread round-robin), so the generator scales past a
// single event loop / input fiber — at high connection counts the SERVER
// must be the bottleneck, not this tool (ISSUE 7). The generator config
// rides the --json line (press_threads/press_callers/...) so BENCH
// records say how the load was made.
//
// --timeout_ms sets the per-request deadline (propagated to the server
// as the remaining-budget meta): tiny values drive the server's
// expired-shed and budget-shed paths from the load tool — watch
// rpc_server_expired_requests / rpc_server_shed_requests in its /vars.
//
// Multi-tenant QoS (ISSUE 8): --tenant/--priority stamp every request's
// identity meta; --tenants=name:weight[:priority],... runs a MIXED load
// where the target --qps splits across tenants by weight (callers too)
// — the overload-isolation soak's shape: one flooding low-priority
// tenant plus a steady high-priority one, in one process. Responses
// carrying TERR_OVERLOAD count as `shed` separately from other
// failures. With more than one tenant, --metrics_csv appends one row
// per tenant per interval (tenant column; the aggregate row says
// "all") and --json adds a per-tenant breakdown.
//
// Grey-failure soaks (ISSUE 20): --server=h:p,h:p,... runs the full
// client-side LB stack (round-robin under the outlier-ejection wrapper)
// over a list:// naming set, so the GENERATOR is the process that
// detects and ejects a degraded backend. Each completed call is
// attributed to the backend that served it (cntl.remote_side()): the
// end-of-run report gains a per-backend picks/errors/p99 table (and a
// press_backends object + rpc_outlier_* counters in --json), and
// --backend_csv=<path> appends per-interval per-backend delta rows —
// the pick-share trace an ejection/reinstatement assertion reads.
//
// While running, one stats line per second (interval qps + windowed
// p50/p99/p999); --metrics_csv=<path> appends the same row per interval
// as CSV (elapsed_s,qps,p50_us,p99_us,p999_us,failed_total,tenant) —
// the BENCH trajectory input. Prints qps achieved + latency percentiles
// at the end; --json for one JSON line.
#include <netinet/in.h>
#include <poll.h>
#include <signal.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

#include "bench_echo.pb.h"
#include "tbase/endpoint.h"
#include "tbase/errno.h"
#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "tici/block_pool.h"
#include "tnet/transport.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "trpc/outlier.h"
#include "trpc/stream.h"
#include "tvar/latency_recorder.h"
#include "tvar/variable.h"

using namespace tpurpc;

namespace {

// In-process numeric tvar read (per-zone LB counters for the report).
int64_t VarInt(const char* name) {
    std::string v;
    if (!Variable::describe_exposed(name, &v)) return 0;
    return atoll(v.c_str());
}

// Minimal blocking HTTP/1.1 GET against the router portal (--via): one
// scrape at end-of-run, so a plain blocking socket with a deadline is
// plenty — no reason to drag the RPC stack into reading its own proxy.
bool PortalGet(const EndPoint& ep, const std::string& path,
               std::string* body) {
    const int fd = ::socket(AF_INET, SOCK_STREAM, 0);
    if (fd < 0) return false;
    timeval tv{2, 0};
    setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
    setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
    sockaddr_in addr;
    endpoint2sockaddr(ep, &addr);
    if (::connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
        ::close(fd);
        return false;
    }
    const std::string req = "GET " + path +
                            " HTTP/1.1\r\nHost: router\r\n"
                            "Connection: close\r\n\r\n";
    if (::send(fd, req.data(), req.size(), 0) != (ssize_t)req.size()) {
        ::close(fd);
        return false;
    }
    std::string raw;
    char chunk[4096];
    for (;;) {
        const ssize_t n = ::recv(fd, chunk, sizeof(chunk), 0);
        if (n <= 0) break;
        raw.append(chunk, (size_t)n);
    }
    ::close(fd);
    const size_t hdr_end = raw.find("\r\n\r\n");
    if (hdr_end == std::string::npos) return false;
    body->assign(raw, hdr_end + 4, std::string::npos);
    return !body->empty();
}

// Pull `"key": <int>` out of the /router json — the two fields we read
// are flat integers, so a substring scan beats a JSON parser here.
int64_t JsonIntField(const std::string& body, const char* key) {
    const std::string needle = std::string("\"") + key + "\":";
    const size_t pos = body.find(needle);
    if (pos == std::string::npos) return -1;
    return atoll(body.c_str() + pos + needle.size());
}

// One traffic class of the generator: its own pacing bucket and stats,
// so per-tenant isolation is measurable from the CLIENT side too. A
// per-tenant payload override (the 4th --tenants spec field, ISSUE 15)
// makes one generator emit MIXED-COST load: a "heavy" tenant flooding
// big bodies inside its request-count rate while a light tenant
// trickles — the shape that proves work-priced admission.
struct TenantGen {
    std::string name;       // empty = no identity stamped
    int priority = -1;      // <0 = unset
    int weight = 1;
    int payload = -1;       // <0 = the global --body_bytes/--payload
    long long qps = 0;      // this tenant's share of the target
    LatencyRecorder lat;
    IOBuf filler;           // this class's request body
    std::atomic<int64_t> tokens{0};
    std::atomic<int64_t> sent{0};
    std::atomic<int64_t> failed{0};
    std::atomic<int64_t> shed{0};  // TERR_OVERLOAD rejections
    std::atomic<int64_t> stale{0};  // TERR_STALE_EPOCH fences (pool_desc)
    // Largest server-suggested backoff seen on a shed: the soak asserts
    // the hint is real (drain-rate-derived), not just the flag floor.
    std::atomic<int64_t> backoff_ms_max{0};
    int64_t granted = 0;
    int64_t last_sent = 0;  // interval reporting
    // --stream_tokens mode: per-class inference-serving latencies —
    // time-to-first-token from the FIRST open attempt, and the gap
    // between consecutive delivered tokens (resume pauses included:
    // both are what the end user of a token stream actually waits).
    LatencyRecorder ttft;
    LatencyRecorder itl;
    std::atomic<int64_t> stream_tokens_rx{0};
    std::atomic<int64_t> stream_resumes{0};
    std::atomic<int64_t> stream_seq_errors{0};
    std::atomic<int64_t> stream_dups{0};
};

// Per-backend client-side stats (ISSUE 20): when --server is a comma
// list the channel runs the full LB stack — outlier tier included — in
// THIS process, and every completed call says which backend served it
// (cntl.remote_side()). The table is how a grey-failure soak watches
// traffic steer off an ejected node and return after reinstatement,
// without trusting the grey node's own telemetry.
struct BackendStat {
    std::atomic<int64_t> picks{0};
    std::atomic<int64_t> errors{0};
    LatencyRecorder lat;
    int64_t last_picks = 0;  // interval deltas (--backend_csv)
    int64_t last_errors = 0;
};
std::mutex g_backend_mu;
std::map<std::string, std::unique_ptr<BackendStat>> g_backends;
std::atomic<bool> g_track_backends{false};

void RecordBackend(const Controller& cntl, int64_t latency_us) {
    if (!g_track_backends.load(std::memory_order_relaxed)) return;
    const EndPoint ep = cntl.remote_side();
    if (ep.port == 0) return;  // failed before any backend was picked
    BackendStat* bs = nullptr;
    {
        std::lock_guard<std::mutex> lock(g_backend_mu);
        auto& slot = g_backends[endpoint2str(ep)];
        if (slot == nullptr) slot.reset(new BackendStat);
        bs = slot.get();
    }
    bs->picks.fetch_add(1, std::memory_order_relaxed);
    if (cntl.Failed()) {
        bs->errors.fetch_add(1, std::memory_order_relaxed);
    } else if (latency_us > 0) {
        bs->lat << latency_us;
    }
}

struct PressCtx {
    benchpb::EchoService_Stub* stub;
    TenantGen* gen;
    std::atomic<bool>* stop;
    int64_t timeout_ms;
    bool pool_desc = false;
    std::string session;  // --sessions: sticky id stamped on every call
    long long stream_tokens = 0;   // --stream_tokens: tokens per stream
    int stream_read_delay_ms = 0;  // --stream_read_delay_ms: slow consumer
};

// Ctrl-C / SIGINT: finish the current interval cleanly — flush the final
// p50/p99/p999 line and --metrics_csv row, join the callers, print the
// summary — instead of dying mid-write with a torn CSV.
volatile sig_atomic_t g_sigint = 0;
void OnSigint(int) { g_sigint = 1; }

// One streamed inference "call" (--stream_tokens, ISSUE 17): open a
// server-push stream, consume the token stream asserting contiguous
// seqs AND deterministic content ("tok:<key>:<seq>"), and drive the
// resume funnel through the SAME StreamCall on EOF/timeout/backend
// death — the generator is the exactly-once prover. Returns true when
// the full stream (all N tokens + EOS) was delivered.
bool StreamOnce(PressCtx* c, TenantGen* g) {
    push_stream::StreamCall call;
    char key[32];
    snprintf(key, sizeof(key), "k%llx",
             (unsigned long long)call.stream_id());
    char payload[96];
    snprintf(payload, sizeof(payload), "stream:%lld:%s",
             c->stream_tokens, key);
    const int64_t t_open = monotonic_time_us();
    uint64_t expect = 0;  // last contiguous seq we verified
    int opens = 0;
    bool ttft_done = false;
    int64_t last_tok_us = 0;
    bool complete = false;
    while (!complete && !c->stop->load(std::memory_order_relaxed)) {
        Controller cntl;
        cntl.set_timeout_ms(c->timeout_ms);
        if (!g->name.empty()) cntl.set_tenant(g->name);
        if (g->priority >= 0) cntl.set_priority(g->priority);
        if (!c->session.empty()) cntl.set_session(c->session);
        call.PrepareOpen(&cntl);
        benchpb::EchoRequest req;
        benchpb::EchoResponse res;
        req.set_send_ts_us(monotonic_time_us());
        req.set_payload(payload);
        c->stub->Echo(&cntl, &req, &res, nullptr);
        if (++opens > 1) {
            g->stream_resumes.fetch_add(1, std::memory_order_relaxed);
        }
        if (cntl.Failed()) {
            // Any open failure is retriable through the funnel: the
            // router/backend that refused may be mid-restart. Bounded
            // so a misconfigured target still terminates.
            if (opens < 25) {
                fiber_usleep(100 * 1000);
                continue;
            }
            break;
        }
        bool reopen = false;
        while (!c->stop->load(std::memory_order_relaxed)) {
            std::string chunk;
            uint64_t seq = 0;
            const int rc = call.Read(
                &chunk, &seq,
                (int)std::max<int64_t>(1, c->timeout_ms));
            if (rc == 0) {
                const int64_t now = monotonic_time_us();
                if (!ttft_done) {
                    g->ttft << now - t_open;
                    ttft_done = true;
                } else {
                    g->itl << now - last_tok_us;
                }
                last_tok_us = now;
                char want[64];
                snprintf(want, sizeof(want), "tok:%s:%llu", key,
                         (unsigned long long)seq);
                if (seq != expect + 1 || chunk != want) {
                    g->stream_seq_errors.fetch_add(
                        1, std::memory_order_relaxed);
                }
                expect = seq;
                g->stream_tokens_rx.fetch_add(1,
                                              std::memory_order_relaxed);
                if (c->stream_read_delay_ms > 0) {
                    // Slow consumer: stops granting credits while
                    // sleeping — the server-side writer must park.
                    fiber_usleep((int64_t)c->stream_read_delay_ms * 1000);
                }
            } else if (rc == 1) {
                complete = expect == (uint64_t)c->stream_tokens;
                break;
            } else if (rc == TERR_EOF || rc == TERR_RPC_TIMEDOUT ||
                       rc == TERR_FAILED_SOCKET) {
                reopen = opens < 25;
                break;
            } else {
                break;  // non-retriable abort
            }
        }
        if (!reopen) break;
    }
    g->stream_dups.fetch_add((int64_t)call.duplicates(),
                             std::memory_order_relaxed);
    if (complete) g->lat << (monotonic_time_us() - t_open);
    return complete;
}

void* PressCaller(void* arg) {
    auto* c = (PressCtx*)arg;
    TenantGen* g = c->gen;
    while (!c->stop->load(std::memory_order_relaxed)) {
        // Token bucket: each call consumes one token (reference
        // rdma_performance client.cpp:68).
        if (g->tokens.fetch_sub(1, std::memory_order_relaxed) <= 0) {
            g->tokens.fetch_add(1, std::memory_order_relaxed);
            fiber_usleep(200);
            continue;
        }
        if (c->stream_tokens > 0) {
            // One paced "call" = one full token stream. A stream cut
            // short by shutdown is neither success nor failure.
            if (StreamOnce(c, g)) {
                g->sent.fetch_add(1, std::memory_order_relaxed);
            } else if (!c->stop->load(std::memory_order_relaxed)) {
                g->failed.fetch_add(1, std::memory_order_relaxed);
            }
            continue;
        }
        Controller cntl;
        cntl.set_timeout_ms(c->timeout_ms);
        if (!g->name.empty()) cntl.set_tenant(g->name);
        if (g->priority >= 0) cntl.set_priority(g->priority);
        if (!c->session.empty()) cntl.set_session(c->session);
        benchpb::EchoRequest req;
        benchpb::EchoResponse res;
        req.set_send_ts_us(monotonic_time_us());
        const size_t payload = g->filler.size();
        if (c->pool_desc) {
            // One-sided descriptor load: pin a fresh pool block per call
            // (lease-managed; EndRPC releases it) so the generator
            // drives the full pin/resolve/release cycle, not a reused
            // buffer.
            IOBuf att;
            char* data = nullptr;
            if (IciBlockPool::AllocatePoolAttachment(payload, &att,
                                                     &data)) {
                memset(data, 'p', payload);
                cntl.set_request_pool_attachment(std::move(att));
            } else {
                cntl.request_attachment().append(g->filler);
            }
        } else {
            cntl.request_attachment().append(g->filler);
        }
        c->stub->Echo(&cntl, &req, &res, nullptr);
        if (cntl.Failed()) {
            RecordBackend(cntl, 0);
            g->failed.fetch_add(1, std::memory_order_relaxed);
            if (cntl.ErrorCode() == TERR_OVERLOAD) {
                g->shed.fetch_add(1, std::memory_order_relaxed);
                const int64_t hint = cntl.suggested_backoff_ms();
                int64_t cur =
                    g->backoff_ms_max.load(std::memory_order_relaxed);
                while (hint > cur &&
                       !g->backoff_ms_max.compare_exchange_weak(
                           cur, hint, std::memory_order_relaxed)) {
                }
            } else if (cntl.ErrorCode() == TERR_STALE_EPOCH) {
                g->stale.fetch_add(1, std::memory_order_relaxed);
            }
        } else {
            const int64_t lat_us = monotonic_time_us() - res.send_ts_us();
            RecordBackend(cntl, lat_us);
            g->lat << lat_us;
            g->sent.fetch_add(1, std::memory_order_relaxed);
        }
    }
    return nullptr;
}

// "--tenants=a:8,b:1", "a:8:7,b:1:1", or "a:8:7:128,b:1:1:65536" ->
// name:weight[:priority[:payload_bytes]] specs. The 4th field gives the
// class its own body size — one generator then emits mixed-COST load.
bool ParseTenantsSpec(const char* spec, int default_priority,
                      std::vector<std::unique_ptr<TenantGen>>* gens) {
    std::string s(spec);
    size_t pos = 0;
    while (pos < s.size()) {
        size_t comma = s.find(',', pos);
        if (comma == std::string::npos) comma = s.size();
        const std::string entry = s.substr(pos, comma - pos);
        pos = comma + 1;
        if (entry.empty()) continue;
        const size_t c1 = entry.find(':');
        if (c1 == std::string::npos || c1 == 0) return false;
        auto g = std::make_unique<TenantGen>();
        g->name = entry.substr(0, c1);
        g->priority = default_priority;
        const size_t c2 = entry.find(':', c1 + 1);
        g->weight = atoi(entry.c_str() + c1 + 1);
        if (g->weight <= 0) return false;
        if (c2 != std::string::npos) {
            g->priority = atoi(entry.c_str() + c2 + 1);
            const size_t c3 = entry.find(':', c2 + 1);
            if (c3 != std::string::npos) {
                g->payload = atoi(entry.c_str() + c3 + 1);
                if (g->payload < 0) return false;
            }
        }
        gens->push_back(std::move(g));
    }
    return !gens->empty();
}

}  // namespace

int main(int argc, char** argv) {
    std::string server_str;
    long long qps = 10000;
    int duration_s = 10;
    int payload = 4096;
    int callers = 8;
    int press_threads = 1;
    long long timeout_ms = 5000;
    bool pooled = false;
    bool pool_desc = false;
    bool json = false;
    const char* metrics_csv = nullptr;
    const char* tenants_spec = nullptr;
    std::string tenant;
    std::string zone;       // --zone: this generator's pod (ISSUE 14)
    std::string dcn_peers;  // --dcn_peers=h:p[,h:p]: cross-pod servers
    std::string via_str;    // --via: a tpu_router front door (ISSUE 16)
    int sessions = 0;       // --sessions: sticky ids stamped per caller
    int priority = -1;
    int max_retry = -1;  // <0 = channel default (3)
    long long stream_tokens = 0;  // --stream_tokens: push-stream mode
    int stream_read_delay_ms = 0;
    const char* blackbox_path = nullptr;  // --blackbox=PATH (ISSUE 19)
    const char* backend_csv = nullptr;    // --backend_csv=PATH (ISSUE 20)
    for (int i = 1; i < argc; ++i) {
        if (strncmp(argv[i], "--metrics_csv=", 14) == 0) {
            metrics_csv = argv[i] + 14;
        }
        if (strncmp(argv[i], "--backend_csv=", 14) == 0) {
            backend_csv = argv[i] + 14;
        }
        if (strncmp(argv[i], "--press_threads=", 16) == 0) {
            press_threads = atoi(argv[i] + 16);
        }
        if (strncmp(argv[i], "--server=", 9) == 0) server_str = argv[i] + 9;
        if (strncmp(argv[i], "--via=", 6) == 0) {
            via_str = argv[i] + 6;
            server_str = via_str;  // the router IS the target
        }
        if (strncmp(argv[i], "--sessions=", 11) == 0) {
            sessions = atoi(argv[i] + 11);
        }
        if (strncmp(argv[i], "--qps=", 6) == 0) qps = atoll(argv[i] + 6);
        if (strncmp(argv[i], "--timeout_ms=", 13) == 0) {
            timeout_ms = atoll(argv[i] + 13);
        }
        if (strncmp(argv[i], "-timeout_ms=", 12) == 0) {
            timeout_ms = atoll(argv[i] + 12);
        }
        if (strncmp(argv[i], "--duration_s=", 13) == 0) {
            duration_s = atoi(argv[i] + 13);
        }
        if (strncmp(argv[i], "--payload=", 10) == 0) {
            payload = atoi(argv[i] + 10);
        }
        // --body_bytes: the cost-model-facing spelling of --payload
        // (ISSUE 15) — the logical bytes half of a request's price.
        if (strncmp(argv[i], "--body_bytes=", 13) == 0) {
            payload = atoi(argv[i] + 13);
        }
        if (strncmp(argv[i], "--callers=", 10) == 0) {
            callers = atoi(argv[i] + 10);
        }
        if (strncmp(argv[i], "--tenant=", 9) == 0) tenant = argv[i] + 9;
        if (strncmp(argv[i], "--zone=", 7) == 0) zone = argv[i] + 7;
        if (strncmp(argv[i], "--dcn_peers=", 12) == 0) {
            dcn_peers = argv[i] + 12;
        }
        if (strncmp(argv[i], "--priority=", 11) == 0) {
            priority = atoi(argv[i] + 11);
        }
        // --max_retry=0 makes every shed/failure a FINAL failure: the
        // generator then emits its raw offered load instead of
        // throttling itself on overload backoffs — what an overload
        // soak needs to hold a flood at Nx capacity.
        if (strncmp(argv[i], "--max_retry=", 12) == 0) {
            max_retry = atoi(argv[i] + 12);
        }
        if (strncmp(argv[i], "--tenants=", 10) == 0) {
            tenants_spec = argv[i] + 10;
        }
        if (strncmp(argv[i], "--stream_tokens=", 16) == 0) {
            stream_tokens = atoll(argv[i] + 16);
        }
        if (strncmp(argv[i], "--stream_read_delay_ms=", 23) == 0) {
            stream_read_delay_ms = atoi(argv[i] + 23);
        }
        if (strcmp(argv[i], "--pooled") == 0) pooled = true;
        if (strcmp(argv[i], "--pool_desc") == 0 ||
            strcmp(argv[i], "--pool-desc") == 0) {
            pool_desc = true;
        }
        // --blackbox=PATH: dump the CLIENT-side flight rings there at
        // exit (and on a fatal signal) — the initiator half of a merged
        // causal timeline.
        if (strncmp(argv[i], "--blackbox=", 11) == 0) {
            blackbox_path = argv[i] + 11;
        }
        // --flag=name=value: tune any registered flag in the PRESS
        // process (mesh_node's --flag twin) — the grey-failure soak
        // enlarges flight_recorder_ring so the in-press EJECT event
        // survives to the end-of-run dump.
        if (strncmp(argv[i], "--flag=", 7) == 0) {
            const std::string kv = argv[i] + 7;
            const size_t eq = kv.find('=');
            if (eq == std::string::npos ||
                !SetFlagValue(kv.substr(0, eq), kv.substr(eq + 1))) {
                fprintf(stderr, "bad --flag %s\n", kv.c_str());
                return 2;
            }
        }
        if (strcmp(argv[i], "--json") == 0) json = true;
    }
    if (server_str.empty()) {
        fprintf(stderr,
                "usage: rpc_press --server=ip:port[,ip:port...] [--qps=N] "
                "[--duration_s=N] [--payload=N] [--callers=N] "
                "[--press_threads=N] [--pooled] [--pool_desc "
                "(alias: --pool-desc)] "
                "[--timeout_ms=N] [--body_bytes=N (alias: --payload)] "
                "[--max_retry=N] [--tenant=NAME] [--priority=0..7] "
                "[--tenants=name:weight[:prio[:payload_bytes]],...] "
                "[--zone=NAME] [--dcn_peers=ip:port,...] "
                "[--via=ip:port] [--sessions=N] "
                "[--stream_tokens=N [--stream_read_delay_ms=N]] "
                "[--blackbox=PATH] [--backend_csv=PATH] "
                "[--flag=name=value] [--json]\n"
                "  --server with a comma list drives a client-side LB "
                "channel (rr + outlier ejection); per-backend picks/"
                "errors/p99 and rpc_outlier_* counters are reported, "
                "--backend_csv appends per-interval per-backend rows\n"
                "  --zone/--dcn_peers: zone-aware LB over the local "
                "server + cross-pod dcn-tier peers; per-zone picks and "
                "spills are reported\n"
                "  --stream_tokens=N: each paced call opens a resumable "
                "server-push stream of N tokens; contiguity is asserted "
                "and TTFT p50/p99 + inter-token p99 reported\n");
        return 1;
    }
    if (blackbox_path != nullptr) {
        flight::SetNodeName("rpc_press");
        flight::InstallCrashHandler(blackbox_path);
    }
    // --server=h:p,h:p (ISSUE 20): a comma list turns the generator into
    // an LB client — the channel below runs the full load-balancer stack
    // (round-robin under the outlier wrapper) over a list:// naming set,
    // so ejection and reinstatement decisions happen IN this process and
    // the per-backend table (--backend_csv / press_backends) shows
    // traffic steering around a grey node. The first entry doubles as
    // the plain EndPoint the non-LB paths keep using.
    std::string server_list;
    if (server_str.find(',') != std::string::npos) {
        server_list = server_str;
        server_str.resize(server_str.find(','));
    }
    EndPoint server;
    if (hostname2endpoint(server_str.c_str(), &server) != 0) {
        fprintf(stderr, "bad server address: %s\n", server_str.c_str());
        return 1;
    }
    // Traffic classes: one per --tenants entry, or the single
    // (possibly anonymous) --tenant/--priority class.
    std::vector<std::unique_ptr<TenantGen>> gens;
    if (tenants_spec != nullptr) {
        if (!ParseTenantsSpec(tenants_spec, priority, &gens)) {
            fprintf(stderr, "bad --tenants spec: %s\n", tenants_spec);
            return 1;
        }
    } else {
        auto g = std::make_unique<TenantGen>();
        g->name = tenant;
        g->priority = priority;
        gens.push_back(std::move(g));
    }
    // Split the target qps (and below, the callers) by weight.
    long long wsum = 0;
    for (const auto& g : gens) wsum += g->weight;
    // Every class gets at least 1 qps (the max(1,...) floors can make
    // the shares sum past --qps at tiny targets — a silent zero-rate
    // tenant would be worse than a slightly-over-target run).
    long long qps_left = qps;
    for (size_t i = 0; i < gens.size(); ++i) {
        gens[i]->qps = i + 1 == gens.size()
                           ? std::max<long long>(1, qps_left)
                           : std::max<long long>(1, qps * gens[i]->weight /
                                                        wsum);
        qps_left -= gens[i]->qps;
    }
    if (press_threads < 1) press_threads = 1;
    if (callers < press_threads) callers = press_threads;
    if (callers < (int)gens.size()) callers = (int)gens.size();
    ChannelOptions copts;
    copts.timeout_ms = timeout_ms;
    if (max_retry >= 0) copts.max_retry = max_retry;
    if (pooled) copts.connection_type = CONNECTION_TYPE_POOLED;
    // Multi-channel generator: each channel pins its own connection so
    // the N connections shard across the server's (and this tool's)
    // epoll loops; a single shared SocketMap socket would serialize all
    // callers through one input fiber. NOT in pooled mode: pooled calls
    // ride fly sockets from the shared per-endpoint pool (the pin would
    // be bypassed and just leak one idle connection per channel) and the
    // pool's FIFO rotation already spreads load across connections.
    copts.pin_connection = press_threads > 1 && !pooled;
    if (pool_desc) {
        // Descriptor traffic needs the registered pool AND an shm-ICI
        // link whose handshake maps it on the server (plain TCP would
        // fall back inline / get TERR_REQUEST).
        if (IciBlockPool::Init() != 0 ||
            IciBlockPool::shm_name()[0] == '\0') {
            fprintf(stderr,
                    "--pool_desc: IciBlockPool init failed (no /dev/shm?)\n");
            return 1;
        }
    }
    // Mixed intra/cross-pod load (ISSUE 14): with --zone/--dcn_peers the
    // generator drives a zone-aware LB channel over a list:// naming set
    // — the local --server tagged with this zone, every --dcn_peers
    // entry tagged zone=remote (reached over dcn-tier sockets). Picks
    // stay local while the local server serves; kill it and the spill
    // counters reported below fire.
    std::string lb_url;
    if (!dcn_peers.empty()) {
        const std::string my_zone = zone.empty() ? "local" : zone;
        SetFlagValue("rpc_zone", my_zone);
        lb_url = "list://" + server_str + " zone=" + my_zone;
        size_t pos = 0;
        while (pos < dcn_peers.size()) {
            size_t comma = dcn_peers.find(',', pos);
            if (comma == std::string::npos) comma = dcn_peers.size();
            const std::string ep = dcn_peers.substr(pos, comma - pos);
            pos = comma + 1;
            if (ep.empty()) continue;
            // Entries may carry their own "ip:port zone=B" tag (space
            // separated); bare addresses default to zone=remote.
            lb_url += "," + ep;
            if (ep.find("zone=") == std::string::npos) {
                lb_url += " zone=remote";
            }
        }
    } else if (!zone.empty()) {
        SetFlagValue("rpc_zone", zone);
    }
    if (lb_url.empty() && !server_list.empty()) {
        lb_url = "list://" + server_list;
    }
    if (!lb_url.empty()) {
        // Client-side outlier tier: seed the rpc_outlier_* counters read
        // below and route health-check revives of ejected sockets
        // through the reinstatement probe ramp.
        outlier::ExposeVars();
        g_track_backends.store(true, std::memory_order_relaxed);
    }
    std::vector<std::unique_ptr<Channel>> channels;
    std::vector<std::unique_ptr<benchpb::EchoService_Stub>> stubs;
    for (int i = 0; i < press_threads; ++i) {
        channels.emplace_back(new Channel);
        const int rc =
            pool_desc ? channels.back()->InitIci(server, &copts)
            : !lb_url.empty()
                ? channels.back()->Init(lb_url.c_str(), "rr", &copts)
                : channels.back()->Init(server, &copts);
        if (rc != 0) {
            if (pool_desc) {
                fprintf(stderr,
                        "--pool_desc: ICI handshake with %s failed (is "
                        "the server on this host with a shared pool?)\n",
                        server_str.c_str());
            }
            return 1;
        }
        stubs.emplace_back(
            new benchpb::EchoService_Stub(channels.back().get()));
    }

    // Per-class request bodies: the spec's payload override, else the
    // global --body_bytes/--payload.
    for (auto& g : gens) {
        const int pbytes = g->payload >= 0 ? g->payload : payload;
        g->filler.append(std::string((size_t)pbytes, 'p'));
    }
    std::atomic<bool> stop{false};
    // Caller -> tenant assignment by weight (every tenant gets at least
    // one caller), channels round-robin underneath.
    std::vector<TenantGen*> assignment;
    for (auto& g : gens) assignment.push_back(g.get());
    while ((int)assignment.size() < callers) {
        // Repeat tenants proportionally to weight until callers filled.
        long long best = -1;
        TenantGen* pick = gens[0].get();
        for (auto& g : gens) {
            long long have = 0;
            for (TenantGen* a : assignment) have += (a == g.get());
            // Deficit = desired share minus current share (scaled).
            const long long deficit =
                (long long)g->weight * (long long)assignment.size() -
                have * wsum;
            if (deficit > best) {
                best = deficit;
                pick = g.get();
            }
        }
        assignment.push_back(pick);
    }
    std::vector<PressCtx> ctxs;
    ctxs.reserve((size_t)callers);
    for (int i = 0; i < callers; ++i) {
        ctxs.push_back(PressCtx{stubs[(size_t)(i % press_threads)].get(),
                                assignment[(size_t)i], &stop,
                                timeout_ms, pool_desc,
                                i < sessions
                                    ? "s" + std::to_string(i)
                                    : std::string(),
                                stream_tokens, stream_read_delay_ms});
    }
    std::vector<fiber_t> tids((size_t)callers);
    for (size_t i = 0; i < tids.size(); ++i) {
        fiber_start_background(&tids[i], nullptr, PressCaller, &ctxs[i]);
    }

    // Per-interval scrape sink (--metrics_csv): one appended row per
    // second feeds the BENCH trajectory; mixed-tenant runs add one row
    // per tenant per interval (tenant column).
    FILE* csv = nullptr;
    if (metrics_csv != nullptr) {
        const bool fresh = access(metrics_csv, F_OK) != 0;
        csv = fopen(metrics_csv, "a");
        if (csv != nullptr && fresh) {
            // Stream columns APPENDED at the end: an operator's script
            // may index qps/p99 positionally (c[1], c[3]).
            fprintf(csv,
                    "elapsed_s,qps,p50_us,p99_us,p999_us,failed,tenant,"
                    "ttft_p50_us,ttft_p99_us,itl_p99_us\n");
        }
    }
    // Per-interval per-backend rows (--backend_csv): interval pick and
    // error DELTAS — the soak's pick-share-recovery assertion reads the
    // tail rows, so cumulative totals (which remember the outage) would
    // be the wrong shape.
    FILE* bcsv = nullptr;
    if (backend_csv != nullptr) {
        const bool fresh = access(backend_csv, F_OK) != 0;
        bcsv = fopen(backend_csv, "a");
        if (bcsv != nullptr && fresh) {
            fprintf(bcsv, "elapsed_s,backend,picks,errors,p99_us\n");
        }
    }

    // Refill by elapsed time (exact pacing for any target, including
    // qps below the 100Hz refill cadence), per tenant class; buckets
    // capped at one second of budget so stalls don't cause unbounded
    // bursts.
    const int64_t t0 = monotonic_time_us();
    const int64_t end = t0 + (int64_t)duration_s * 1000 * 1000;
    int64_t next_report_us = t0 + 1000 * 1000;
    int64_t agg_last_sent = 0;
    const auto report = [&](int64_t now) {
        int64_t total_sent = 0, total_failed = 0;
        for (auto& g : gens) {
            total_sent += g->sent.load(std::memory_order_relaxed);
            total_failed += g->failed.load(std::memory_order_relaxed);
        }
        const int64_t iqps = total_sent - agg_last_sent;
        agg_last_sent = total_sent;
        const long long elapsed_s = (now - t0) / 1000000;
        // Aggregate percentiles: single-class runs report that class;
        // mixed runs report the first (it also gets per-tenant rows).
        long long p50 = 0, p99 = 0, p999 = 0;
        {
            int64_t cnt = 0;
            for (auto& g : gens) {
                // Use the class with the most samples as the headline.
                if (g->lat.count() > cnt) {
                    cnt = g->lat.count();
                    p50 = g->lat.latency_percentile(0.5);
                    p99 = g->lat.latency_percentile(0.99);
                    p999 = g->lat.latency_percentile(0.999);
                }
            }
        }
        // Headline stream latencies: the class with the most tokens.
        long long ttft50 = 0, ttft99 = 0, itl99 = 0;
        {
            int64_t cnt = -1;
            for (auto& g : gens) {
                if (g->ttft.count() > cnt) {
                    cnt = g->ttft.count();
                    ttft50 = g->ttft.latency_percentile(0.5);
                    ttft99 = g->ttft.latency_percentile(0.99);
                    itl99 = g->itl.latency_percentile(0.99);
                }
            }
        }
        printf("t=%llds qps=%lld p50=%lldus p99=%lldus p999=%lldus "
               "failed=%lld\n",
               elapsed_s, (long long)iqps, p50, p99, p999,
               (long long)total_failed);
        fflush(stdout);
        if (csv != nullptr) {
            fprintf(csv,
                    "%lld,%lld,%lld,%lld,%lld,%lld,all,%lld,%lld,%lld\n",
                    elapsed_s, (long long)iqps, p50, p99, p999,
                    (long long)total_failed, ttft50, ttft99, itl99);
            if (gens.size() > 1) {
                for (auto& g : gens) {
                    const int64_t s = g->sent.load(std::memory_order_relaxed);
                    fprintf(csv,
                            "%lld,%lld,%lld,%lld,%lld,%lld,%s,"
                            "%lld,%lld,%lld\n",
                            elapsed_s, (long long)(s - g->last_sent),
                            (long long)g->lat.latency_percentile(0.5),
                            (long long)g->lat.latency_percentile(0.99),
                            (long long)g->lat.latency_percentile(0.999),
                            (long long)g->failed.load(
                                std::memory_order_relaxed),
                            g->name.empty() ? "default" : g->name.c_str(),
                            (long long)g->ttft.latency_percentile(0.5),
                            (long long)g->ttft.latency_percentile(0.99),
                            (long long)g->itl.latency_percentile(0.99));
                    g->last_sent = s;
                }
            }
            fflush(csv);
        }
        if (bcsv != nullptr) {
            std::lock_guard<std::mutex> lock(g_backend_mu);
            for (auto& kv : g_backends) {
                BackendStat* b = kv.second.get();
                const int64_t p = b->picks.load(std::memory_order_relaxed);
                const int64_t e =
                    b->errors.load(std::memory_order_relaxed);
                fprintf(bcsv, "%lld,%s,%lld,%lld,%lld\n", elapsed_s,
                        kv.first.c_str(), (long long)(p - b->last_picks),
                        (long long)(e - b->last_errors),
                        (long long)b->lat.latency_percentile(0.99));
                b->last_picks = p;
                b->last_errors = e;
            }
            fflush(bcsv);
        }
    };
    signal(SIGINT, OnSigint);  // clean early stop (full final report)
    while (monotonic_time_us() < end && !g_sigint) {
        const int64_t now = monotonic_time_us();
        for (auto& g : gens) {
            const int64_t should = (now - t0) * g->qps / 1000000;
            if (should > g->granted) {
                g->tokens.fetch_add(should - g->granted,
                                    std::memory_order_relaxed);
                g->granted = should;
            }
            int64_t cur = g->tokens.load(std::memory_order_relaxed);
            if (cur > g->qps) {
                g->tokens.fetch_sub(cur - g->qps,
                                    std::memory_order_relaxed);
            }
        }
        if (now >= next_report_us) {
            next_report_us += 1000 * 1000;
            report(now);
        }
        usleep(10 * 1000);
    }
    // The loop exits AT the deadline (or on SIGINT), so the last
    // interval would otherwise never be reported — an N-second run must
    // yield N rows, and an interrupted run must still end with a
    // complete row rather than a torn write.
    report(monotonic_time_us());
    if (csv != nullptr) fclose(csv);
    if (bcsv != nullptr) fclose(bcsv);
    stop.store(true, std::memory_order_relaxed);
    for (auto tid : tids) fiber_join(tid, nullptr);
    const double secs = (double)(monotonic_time_us() - t0) / 1e6;
    int64_t total_sent = 0, total_failed = 0, total_shed = 0;
    int64_t total_stale = 0;
    for (auto& g : gens) {
        total_sent += g->sent.load();
        total_failed += g->failed.load();
        total_shed += g->shed.load();
        total_stale += g->stale.load();
    }
    const double achieved = (double)total_sent / secs;
    int64_t backoff_max = 0;
    for (auto& g : gens) {
        backoff_max = std::max(backoff_max, g->backoff_ms_max.load());
    }
    // Headline percentiles from the largest class (see report()).
    const TenantGen* head = gens[0].get();
    for (auto& g : gens) {
        if (g->lat.count() > head->lat.count()) head = g.get();
    }
    int64_t stream_rx = 0, stream_resumes = 0, stream_seq_errors = 0;
    int64_t stream_dups = 0;
    const TenantGen* shead = gens[0].get();  // most-token stream class
    for (auto& g : gens) {
        stream_rx += g->stream_tokens_rx.load();
        stream_resumes += g->stream_resumes.load();
        stream_seq_errors += g->stream_seq_errors.load();
        stream_dups += g->stream_dups.load();
        if (g->ttft.count() > shead->ttft.count()) shead = g.get();
    }
    // --via: one scrape of the router's own view — backend-measured p99
    // and the hedge count — then the router-added latency is simply
    // client-observed p99 minus what the backends took.
    int64_t via_backend_p99 = -1, via_hedges = -1, via_added_p99 = -1;
    if (!via_str.empty()) {
        std::string rj;
        if (PortalGet(server, "/router?format=json", &rj)) {
            via_backend_p99 = JsonIntField(rj, "backend_p99_us");
            via_hedges = JsonIntField(rj, "hedges");
            const int64_t client_p99 = head->lat.latency_percentile(0.99);
            if (via_backend_p99 >= 0 && client_p99 > 0) {
                via_added_p99 =
                    std::max<int64_t>(0, client_p99 - via_backend_p99);
            }
        } else {
            fprintf(stderr, "--via: scrape of %s/router failed\n",
                    via_str.c_str());
        }
    }
    if (json) {
        // Generator config rides along so a recorded line is
        // reproducible: the same qps from 1 vs 16 connections stresses
        // completely different server paths.
        printf("{\"press_qps\": %.0f, \"press_target_qps\": %lld, "
               "\"press_failed\": %lld, \"press_shed\": %lld, "
               "\"press_backoff_ms_max\": %lld, "
               "\"press_p50_us\": %lld, "
               "\"press_p99_us\": %lld, \"press_p999_us\": %lld, "
               "\"press_threads\": %d, \"press_callers\": %d, "
               "\"press_payload\": %d, \"press_pooled\": %d, "
               "\"press_pool_desc\": %d, \"press_stale_epoch\": %lld",
               achieved, qps, (long long)total_failed,
               (long long)total_shed, (long long)backoff_max,
               (long long)head->lat.latency_percentile(0.5),
               (long long)head->lat.latency_percentile(0.99),
               (long long)head->lat.latency_percentile(0.999),
               press_threads, callers, payload, pooled ? 1 : 0,
               pool_desc ? 1 : 0, (long long)total_stale);
        if (stream_tokens > 0) {
            printf(", \"press_ttft_us\": {\"p50\": %lld, \"p99\": %lld}, "
                   "\"press_itl_us\": {\"p99\": %lld}, "
                   "\"press_stream_tokens\": %lld, "
                   "\"press_stream_resumes\": %lld, "
                   "\"press_stream_seq_errors\": %lld, "
                   "\"press_stream_dups\": %lld",
                   (long long)shead->ttft.latency_percentile(0.5),
                   (long long)shead->ttft.latency_percentile(0.99),
                   (long long)shead->itl.latency_percentile(0.99),
                   (long long)stream_rx, (long long)stream_resumes,
                   (long long)stream_seq_errors, (long long)stream_dups);
        }
        if (!via_str.empty()) {
            printf(", \"press_via_p99_us\": %lld, "
                   "\"press_via_backend_p99_us\": %lld, "
                   "\"press_hedges\": %lld, \"press_sessions\": %d",
                   (long long)via_added_p99, (long long)via_backend_p99,
                   (long long)via_hedges, sessions);
        }
        if (!dcn_peers.empty()) {
            printf(", \"press_zone\": \"%s\", "
                   "\"press_zone_local_picks\": %lld, "
                   "\"press_zone_spills\": %lld, "
                   "\"press_dcn_out_bytes\": %lld",
                   zone.empty() ? "local" : zone.c_str(),
                   (long long)VarInt("rpc_lb_zone_local_picks"),
                   (long long)VarInt("rpc_lb_zone_spills"),
                   (long long)transport_stats::out_bytes(TierDcn()));
        }
        if (g_track_backends.load(std::memory_order_relaxed)) {
            // The outlier counters are CLIENT-side: the LB channel (and
            // its ejection decisions) live in this process.
            printf(", \"press_outlier_ejections\": %lld, "
                   "\"press_outlier_reinstatements\": %lld, "
                   "\"press_outlier_ejected_now\": %lld, "
                   "\"press_retry_budget_exhausted\": %lld, "
                   "\"press_backends\": {",
                   (long long)VarInt("rpc_outlier_ejections"),
                   (long long)VarInt("rpc_outlier_reinstatements"),
                   (long long)VarInt("rpc_outlier_ejected_now"),
                   (long long)VarInt("rpc_retry_budget_exhausted"));
            std::lock_guard<std::mutex> lock(g_backend_mu);
            bool first = true;
            for (auto& kv : g_backends) {
                BackendStat* b = kv.second.get();
                printf("%s\"%s\": {\"picks\": %lld, \"errors\": %lld, "
                       "\"p50_us\": %lld, \"p99_us\": %lld}",
                       first ? "" : ", ", kv.first.c_str(),
                       (long long)b->picks.load(),
                       (long long)b->errors.load(),
                       (long long)b->lat.latency_percentile(0.5),
                       (long long)b->lat.latency_percentile(0.99));
                first = false;
            }
            printf("}");
        }
        if (gens.size() > 1 || !gens[0]->name.empty()) {
            printf(", \"press_tenants\": {");
            for (size_t i = 0; i < gens.size(); ++i) {
                const auto& g = gens[i];
                printf("%s\"%s\": {\"qps\": %.0f, \"target_qps\": %lld, "
                       "\"priority\": %d, \"payload\": %lld, "
                       "\"sent\": %lld, "
                       "\"failed\": %lld, \"shed\": %lld, "
                       "\"backoff_ms_max\": %lld, "
                       "\"p50_us\": %lld, \"p99_us\": %lld}",
                       i == 0 ? "" : ", ",
                       g->name.empty() ? "default" : g->name.c_str(),
                       (double)g->sent.load() / secs, g->qps, g->priority,
                       (long long)g->filler.size(),
                       (long long)g->sent.load(),
                       (long long)g->failed.load(),
                       (long long)g->shed.load(),
                       (long long)g->backoff_ms_max.load(),
                       (long long)g->lat.latency_percentile(0.5),
                       (long long)g->lat.latency_percentile(0.99));
            }
            printf("}");
        }
        printf("}\n");
    } else {
        printf("sent %lld ok (%lld failed, %lld shed, %lld stale-epoch) "
               "in %.1fs: %.0f qps (target %lld, %d channels x %d "
               "callers%s)\n",
               (long long)total_sent, (long long)total_failed,
               (long long)total_shed, (long long)total_stale, secs,
               achieved, qps, press_threads, callers,
               pool_desc ? ", pool-desc" : "");
        printf("latency_us: p50 %lld  p99 %lld  p999 %lld  max %lld\n",
               (long long)head->lat.latency_percentile(0.5),
               (long long)head->lat.latency_percentile(0.99),
               (long long)head->lat.latency_percentile(0.999),
               (long long)head->lat.max_latency());
        if (stream_tokens > 0) {
            printf("streams: tokens %lld  resumes %lld  seq_errors %lld "
                   " dups %lld  ttft_us p50 %lld p99 %lld  itl_us p99 "
                   "%lld\n",
                   (long long)stream_rx, (long long)stream_resumes,
                   (long long)stream_seq_errors, (long long)stream_dups,
                   (long long)shead->ttft.latency_percentile(0.5),
                   (long long)shead->ttft.latency_percentile(0.99),
                   (long long)shead->itl.latency_percentile(0.99));
        }
        if (!via_str.empty()) {
            printf("via router %s: client p99 %lldus, backend p99 "
                   "%lldus, router-added p99 %lldus, hedges %lld\n",
                   via_str.c_str(),
                   (long long)head->lat.latency_percentile(0.99),
                   (long long)via_backend_p99, (long long)via_added_p99,
                   (long long)via_hedges);
        }
        if (!dcn_peers.empty()) {
            printf("zone %s: local_picks %lld  spills %lld  "
                   "dcn_out_bytes %lld\n",
                   zone.empty() ? "local" : zone.c_str(),
                   (long long)VarInt("rpc_lb_zone_local_picks"),
                   (long long)VarInt("rpc_lb_zone_spills"),
                   (long long)transport_stats::out_bytes(TierDcn()));
        }
        if (g_track_backends.load(std::memory_order_relaxed)) {
            printf("outliers: ejections %lld  reinstatements %lld  "
                   "ejected_now %lld\n",
                   (long long)VarInt("rpc_outlier_ejections"),
                   (long long)VarInt("rpc_outlier_reinstatements"),
                   (long long)VarInt("rpc_outlier_ejected_now"));
            std::lock_guard<std::mutex> lock(g_backend_mu);
            for (auto& kv : g_backends) {
                BackendStat* b = kv.second.get();
                printf("  backend %-21s picks=%lld errors=%lld "
                       "p99=%lldus\n",
                       kv.first.c_str(), (long long)b->picks.load(),
                       (long long)b->errors.load(),
                       (long long)b->lat.latency_percentile(0.99));
            }
        }
        for (auto& g : gens) {
            if (gens.size() <= 1) break;
            printf("  tenant %-12s prio=%d target=%lld qps=%.0f "
                   "sent=%lld failed=%lld shed=%lld p99=%lldus\n",
                   g->name.empty() ? "default" : g->name.c_str(),
                   g->priority, (long long)g->qps,
                   (double)g->sent.load() / secs, (long long)g->sent.load(),
                   (long long)g->failed.load(), (long long)g->shed.load(),
                   (long long)g->lat.latency_percentile(0.99));
        }
    }
    if (blackbox_path != nullptr) {
        flight::DumpToConfiguredPath();
    }
    return 0;
}
