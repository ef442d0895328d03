#include "thttp/builtin_services.h"

#include <malloc.h>

#include <algorithm>
#include <cctype>
#include <map>
#include <vector>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <string>

#include "tbase/cpu_profiler.h"
#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/heap_profiler.h"
#include "tbase/symbolize.h"
#include "tnet/event_dispatcher.h"
#include "tbase/thread_stacks.h"
#include "tfiber/contention_profiler.h"
#include "tfiber/fiber.h"
#include "thttp/http_message.h"
#include "thttp/http_protocol.h"
#include "tfiber/task_group.h"
#include "tfiber/task_meta.h"
#include "tfiber/task_tracer.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tnet/fault_injection.h"
#include "tnet/input_messenger.h"
#include "tnet/socket.h"
#include "trpc/collective.h"
#include "trpc/load_balancer.h"
#include "trpc/outlier.h"
#include "trpc/stream.h"
#include "trpc/rpcz_stitch.h"
#include "trpc/server.h"
#include "trpc/span.h"
#include "tvar/series.h"
#include "tvar/stage_recorder.h"
#include "tvar/variable.h"

DECLARE_bool(chaos_enabled);

namespace tpurpc {

namespace {

void HandleIndex(Server*, const HttpRequest&, HttpResponse* res) {
    res->set_content_type("text/plain");
    res->Append(
        "tpu-rpc server portal\n"
        "\n"
        "/health       liveness\n"
        "/status       per-method stats (?format=json machine form)\n"
        "/vars         exposed variables (/vars/<name> for one;\n"
        "              ?series=<name> 60s/60min/24h ring as JSON)\n"
        "/flags        runtime flags (/flags/<name>?setvalue=v to set)\n"
        "/connections  accepted connections + per-socket I/O attribution\n"
        "/loops        event-dispatcher + fiber-scheduler telemetry\n"
        "/tenants      multi-tenant QoS: cost quotas, fair-queue depth,\n"
        "              measured queue delay + drain-rate backoff,\n"
        "              per-tenant admitted/shed/queued/p99 + cost\n"
        "              units + gradient concurrency limit\n"
        "              (?format=json machine form)\n"
        "/rpcz         sampled per-RPC spans (enable_rpcz flag;\n"
        "              ?trace_id=N filter, &format=json machine form)\n"
        "/rpcz/trace/<id>  ONE cross-host stitched timeline for a trace\n"
        "              (fans out over -rpcz_peers + known remotes)\n"
        "/fibers       fiber runtime introspection (?st=1: stacks)\n"
        "/threads      pthread stack dump\n"
        "/version      build identification\n"
        "/memory       allocator statistics\n"
        "/hotspots     profiling (/hotspots/cpu?seconds=N,\n"
        "              /hotspots/heap, /hotspots/growth,\n"
        "              /hotspots/contention)\n"
        "/chaos        fault injection (?enable=1&seed=N&plan=...&peers=...)\n"
        "/blackbox     flight-recorder rings: newest events per thread\n"
        "              (?format=json: full ring contents for\n"
        "              blackbox_merge.py)\n"
        "/pools        zero-copy pool state: live pinned-block leases\n"
        "              (with direction: req/rsp), per-class slab\n"
        "              occupancy, mapped peer pools + epochs, and the\n"
        "              transport-tier byte attribution\n"
        "              (?format=json machine form)\n"
        "/streams      push-stream tier: rpc_stream_* counters, replay-\n"
        "              ring high-water, live server/client stream rows\n"
        "              (?format=json machine form)\n"
        "/outliers     client-side outlier ejection: per-backend state\n"
        "              (healthy/ejected/probing/ramping), latency EWMAs,\n"
        "              ejection reasons + windows, probe progress\n"
        "              (?format=json machine form)\n"
        "/metrics      prometheus exposition\n");
}

void HandleHealth(Server*, const HttpRequest&, HttpResponse* res) {
    res->set_content_type("text/plain");
    res->Append("OK\n");
}

// /threads: pthread stack dump (reference builtin/threads_service.cpp
// runs pstack; we self-inspect via SIGURG + the fp chain).
void HandleThreads(Server*, const HttpRequest&, HttpResponse* res) {
    res->set_content_type("text/plain");
    res->Append(DumpThreadStacks());
}

void HandleVersion(Server*, const HttpRequest&, HttpResponse* res) {
    res->set_content_type("text/plain");
    res->Append("tpu-rpc 1.0 (bRPC-capability TPU-native framework)\n");
}

// /memory: allocator + pool stats (reference builtin/memory_service).
void HandleMemory(Server*, const HttpRequest&, HttpResponse* res) {
    res->set_content_type("text/plain");
    char line[256];
#if defined(__GLIBC__) && defined(__GLIBC_PREREQ) && __GLIBC_PREREQ(2, 33)
    struct mallinfo2 mi = mallinfo2();
    snprintf(line, sizeof(line),
             "malloc arena: %zu\nin use: %zu\nfree chunks: %zu\n"
             "mmap'd: %zu\n",
             (size_t)mi.arena, (size_t)mi.uordblks, (size_t)mi.fordblks,
             (size_t)mi.hblkhd);
    res->Append(line);
#endif
    snprintf(line, sizeof(line),
             "iobuf tls cached blocks (this thread): %zu\n",
             IOBuf::tls_cached_blocks());
    res->Append(line);
    snprintf(line, sizeof(line), "fiber slots allocated: %zu\n",
             ResourcePool<TaskMeta>::singleton()->size());
    res->Append(line);
}

// ---------------- /hotspots (reference hotspots_service.cpp) ----------------

void HandleHotspotsIndex(Server*, const HttpRequest&, HttpResponse* res) {
    res->set_content_type("text/plain");
    res->Append(
        "profiling\n"
        "\n"
        "/hotspots/cpu?seconds=N   sample all threads for N seconds\n"
        "                          (default 2, max 30) and show the\n"
        "                          symbolized flat profile\n"
        "/hotspots/heap            sampled LIVE bytes by allocation\n"
        "                          stack (-heap_profiler_sample_bytes;\n"
        "                          ?raw=1 for the offline-symbolizable\n"
        "                          dump with /proc/self/maps)\n"
        "/hotspots/growth          cumulative sampled allocations since\n"
        "                          the last ?reset=1 (churn view)\n"
        "/hotspots/contention      fiber-mutex wait sites since the\n"
        "                          last view (?reset=1 to only clear)\n");
}

// /hotspots/cpu: in-server profile run + symbolization. Samples every
// running thread via SIGPROF for `seconds`, then aggregates leaf PCs and
// renders function names (tbase/symbolize.h) — no offline step.
void HandleHotspotsCpu(Server*, const HttpRequest& req, HttpResponse* res) {
    res->set_content_type("text/plain");
    int seconds = atoi(req.QueryParam("seconds").c_str());
    if (seconds <= 0) seconds = 2;
    if (seconds > 30) seconds = 30;
    if (StartCpuProfiler() != 0) {
        res->status = 503;
        res->Append("another profile run is in progress\n");
        return;
    }
    fiber_usleep((int64_t)seconds * 1000 * 1000);
    const std::string dump = StopCpuProfilerToString();
    // Dump: "pc fp1 fp2...\n" per sample until the "--- maps ---" line.
    std::map<uintptr_t, int64_t> by_leaf;
    int64_t nsamples = 0;
    size_t pos = 0;
    while (pos < dump.size()) {
        size_t eol = dump.find('\n', pos);
        if (eol == std::string::npos) eol = dump.size();
        if (dump.compare(pos, 3, "---") == 0) break;
        const uintptr_t leaf = strtoull(dump.c_str() + pos, nullptr, 16);
        if (leaf != 0) {
            ++nsamples;
            ++by_leaf[leaf];
        }
        pos = eol + 1;
    }
    std::vector<std::pair<int64_t, uintptr_t>> top;
    top.reserve(by_leaf.size());
    for (const auto& kv : by_leaf) top.push_back({kv.second, kv.first});
    std::sort(top.rbegin(), top.rend());
    if (top.size() > 40) top.resize(40);
    char line[512];
    snprintf(line, sizeof(line),
             "cpu profile: %lld samples over %ds (997Hz, all threads)\n\n"
             "%8s %6s  %s\n",
             (long long)nsamples, seconds, "samples", "%", "function");
    res->Append(line);
    for (const auto& e : top) {
        snprintf(line, sizeof(line), "%8lld %5.1f%%  %s\n",
                 (long long)e.first,
                 nsamples > 0 ? 100.0 * (double)e.first / (double)nsamples
                              : 0.0,
                 SymbolizePc(e.second).c_str());
        res->Append(line);
    }
}

// /hotspots/heap and /hotspots/growth: the sampling heap profiler
// (tbase/heap_profiler.h). Default view symbolizes in-server like
// /hotspots/cpu; ?raw=1 returns the pprof-style dump (stacks + maps)
// for tools/symbolize_prof.py.
void HandleHotspotsHeap(Server*, const HttpRequest& req, HttpResponse* res) {
    res->set_content_type("text/plain");
    if (!HeapProfilerActive()) {
        res->Append(
            "heap profiler is off — set -heap_profiler_sample_bytes > 0\n"
            "(e.g. /flags/heap_profiler_sample_bytes?setvalue=524288)\n");
        return;
    }
    if (req.QueryParam("raw") == "1") {
        res->Append(HeapProfileRaw(/*growth=*/false));
        return;
    }
    res->Append(HeapProfileSymbolized(/*growth=*/false));
}

void HandleHotspotsGrowth(Server*, const HttpRequest& req,
                          HttpResponse* res) {
    res->set_content_type("text/plain");
    if (req.QueryParam("reset") == "1") {
        ResetHeapGrowth();
        res->Append("growth counters reset\n");
        return;
    }
    if (!HeapProfilerActive()) {
        res->Append(
            "heap profiler is off — set -heap_profiler_sample_bytes > 0\n");
        return;
    }
    if (req.QueryParam("raw") == "1") {
        res->Append(HeapProfileRaw(/*growth=*/true));
        return;
    }
    res->Append(HeapProfileSymbolized(/*growth=*/true));
}

// /loops: where event-loop and scheduler cycles go — per-epoll-loop
// wake/dispatch telemetry and per-worker-pool scheduling counters
// (ISSUE 6). The same numbers are exported as labelled families
// (rpc_dispatcher_*, rpc_scheduler_*) on /metrics and as
// /vars?series=<family>_<label>_<value> rings. ?reset=1 clears the
// run-queue high-waters (counters stay cumulative).
void HandleLoops(Server*, const HttpRequest& req, HttpResponse* res) {
    res->set_content_type("text/plain");
    if (req.QueryParam("reset") == "1") {
        TaskControl::ForEachPool(
            [](int, TaskControl* c, void*) {
                c->reset_runqueue_highwater();
            },
            nullptr);
        res->Append("run-queue high-waters reset\n");
        return;
    }
    res->Append(
        "event dispatchers (epoll loops)\n"
        "loop  cpu   epoll_waits   events      wakeups  batch  "
        "ev/wake p50/p99   wake->dispatch us p50/p99/max\n");
    EventDispatcher::ForEachLoop(
        [](int idx, const EventDispatcher::LoopStats& st, void* arg) {
            auto* r = (HttpResponse*)arg;
            char line[256];
            snprintf(line, sizeof(line),
                     "%-5d %-5d %-13lld %-11lld %-8lld %-6lld "
                     "%lld/%lld%*s%lld/%lld/%lld\n",
                     idx, st.cpu, (long long)st.epoll_waits,
                     (long long)st.events, (long long)st.wakeups,
                     (long long)st.batch_capacity,
                     (long long)st.events_per_wake->latency_percentile(0.5),
                     (long long)st.events_per_wake->latency_percentile(0.99),
                     10, "",
                     (long long)st.wake_to_dispatch_us->latency_percentile(
                         0.5),
                     (long long)st.wake_to_dispatch_us->latency_percentile(
                         0.99),
                     (long long)st.wake_to_dispatch_us->max_latency());
            r->Append(line);
        },
        res);
    {
        // Run-to-completion dispatch (ISSUE 7): messages processed on the
        // input fiber, budget overflows that fanned out, and server
        // handlers that ran inline. tests/test_raw_speed.py asserts
        // inline_dispatches goes nonzero under echo load.
        char line[192];
        snprintf(line, sizeof(line),
                 "\nrun-to-completion dispatch\n"
                 "inline_dispatches: %lld  inline_overflows: %lld  "
                 "inline_handlers: %lld  coalesced_writes: %lld\n",
                 (long long)inline_dispatch::dispatches(),
                 (long long)inline_dispatch::overflows(),
                 (long long)inline_dispatch::handler_inlines(),
                 (long long)SocketCoalescedWrites());
        res->Append(line);
    }
    res->Append(
        "\nfiber scheduler pools\n"
        "pool  workers  live_fibers  steals      remote_overflows  "
        "urgent_handoffs  runq_highwater\n");
    TaskControl::ForEachPool(
        [](int tag, TaskControl* c, void* arg) {
            auto* r = (HttpResponse*)arg;
            char line[256];
            snprintf(line, sizeof(line),
                     "%-5d %-8d %-12lld %-11lld %-17lld %-16lld %lld\n",
                     tag, c->concurrency(), (long long)c->nfibers.load(),
                     (long long)c->steals(),
                     (long long)c->remote_overflows(),
                     (long long)c->urgent_handoffs(),
                     (long long)c->runqueue_highwater());
            r->Append(line);
        },
        res);
}

void HandleHotspotsContention(Server*, const HttpRequest& req,
                              HttpResponse* res) {
    if (req.QueryParam("reset") == "1") {
        ResetContentionProfile();
        res->set_content_type("text/plain");
        res->Append("contention counters reset\n");
        return;
    }
    if (req.QueryParam("format") == "json") {
        res->set_content_type("application/json");
        res->Append(ContentionProfileJson());
        // Same fresh-window semantics as the text view.
        ResetContentionProfile();
        return;
    }
    res->set_content_type("text/plain");
    res->Append(ContentionProfileText());
    // Each view starts a fresh window (matches the reference's
    // per-request contention observation).
    ResetContentionProfile();
}

// /blackbox: the flight recorder's live view — newest events per thread
// ring as text, or the full ring contents as JSON (?format=json; what
// blackbox_merge.py fetches from survivors of a crash drill).
void HandleBlackbox(Server*, const HttpRequest& req, HttpResponse* res) {
    if (req.QueryParam("format") == "json") {
        res->set_content_type("application/json");
        std::string out;
        flight::DumpJson(&out);
        res->Append(out);
        return;
    }
    res->set_content_type("text/plain");
    std::string out;
    flight::DumpText(&out);
    res->Append(out);
}

// /fibers: live fiber-runtime introspection; ?st=1 adds per-fiber stack
// dumps (TaskTracer — reference /bthreads?st=1, bthread/task_tracer.h).
void HandleFibers(Server*, const HttpRequest& req, HttpResponse* res) {
    res->set_content_type("text/plain");
    TaskControl::ForEachPool(
        [](int tag, TaskControl* c, void* arg) {
            auto* r = (HttpResponse*)arg;
            char line[256];
            snprintf(line, sizeof(line),
                     "pool tag=%d  workers: %d  live_fibers: %lld\n", tag,
                     c->concurrency(), (long long)c->nfibers.load());
            r->Append(line);
        },
        res);
    char line[128];
    snprintf(line, sizeof(line), "fiber_slots_allocated: %zu\n",
             ResourcePool<TaskMeta>::singleton()->size());
    res->Append(line);
    if (req.QueryParam("st") == "1") {
        res->Append("\n");
        res->Append(DumpFiberStacks());
    }
}

void HandleRpcz(Server*, const HttpRequest& req, HttpResponse* res) {
    const std::string t = req.QueryParam("trace_id");
    const uint64_t trace = t.empty() ? 0 : strtoull(t.c_str(), nullptr, 10);
    if (req.QueryParam("format") == "json") {
        // Machine-readable spans — what the cross-host stitcher scrapes.
        res->set_content_type("application/json");
        res->Append(RenderRpczJson(trace));
        return;
    }
    res->set_content_type("text/plain");
    res->Append(RenderRpcz(trace));
}

// /rpcz/trace/<id>: ONE stitched timeline for a trace — fans out over
// -rpcz_peers + SocketMap remotes, merges every host's spans, normalizes
// clocks via the parent-child send/recv envelopes.
void HandleRpczTrace(Server*, const HttpRequest& req, HttpResponse* res) {
    res->set_content_type("text/plain");
    const char* prefix = "/rpcz/trace/";
    uint64_t trace = 0;
    if (req.path.size() > strlen(prefix)) {
        trace = strtoull(req.path.c_str() + strlen(prefix), nullptr, 10);
    }
    if (trace == 0) {
        res->status = 400;
        res->Append("usage: /rpcz/trace/<trace_id>\n");
        return;
    }
    res->Append(RenderStitchedTrace(trace));
}

void HandleStatus(Server* server, const HttpRequest& req,
                  HttpResponse* res) {
    // ?format=json: the machine form — the benchmark and the soak tests
    // consume per-method MethodStatus without scraping the text table.
    // Method names are pb identifiers + '_', so no JSON escaping needed.
    if (req.QueryParam("format") == "json") {
        res->set_content_type("application/json");
        std::ostringstream os;
        os << "{\"draining\":" << (server->draining() ? 1 : 0)
           << ",\"nprocessing\":" << server->nprocessing.load()
           << ",\"methods\":{";
        bool first = true;
        for (const auto& kv : server->methods()) {
            const MethodStatus& st = *kv.second.status;
            if (!first) os << ",";
            first = false;
            os << "\"" << kv.first << "\":{"
               << "\"count\":" << st.latency.count()
               << ",\"qps\":" << st.latency.qps()
               << ",\"concurrency\":" << st.concurrency.load()
               << ",\"max_concurrency\":" << st.max_concurrency()
               << ",\"errors\":" << st.nerror.load()
               << ",\"rejected\":" << st.nrejected.load()
               << ",\"expired\":" << st.nexpired.load()
               << ",\"shed\":" << st.nshed.load() << ",\"latency_us\":{"
               << "\"p50\":" << st.latency.latency_percentile(0.5)
               << ",\"p99\":" << st.latency.latency_percentile(0.99)
               << ",\"p999\":" << st.latency.latency_percentile(0.999)
               << ",\"max\":" << st.latency.max_latency() << "}}";
        }
        // The stage clock's table (tvar/stage_recorder.h): cumulative
        // per-stage histograms; two scrapes difference to a window.
        os << "},\"stages\":" << stage::DumpJson() << "}";
        res->Append(os.str());
        return;
    }
    res->set_content_type("text/plain");
    char line[512];
    // Lifecycle state first: "draining: 1" means a graceful shutdown or
    // rebalance announced GOAWAYs and clients are steering away.
    snprintf(line, sizeof(line), "draining: %d\nnprocessing: %lld\n\n",
             server->draining() ? 1 : 0,
             (long long)server->nprocessing.load());
    res->Append(line);
    for (const auto& kv : server->methods()) {
        const MethodStatus& st = *kv.second.status;
        snprintf(line, sizeof(line),
                 "%s\n"
                 "  count: %lld  qps: %lld  concurrency: %lld/%lld"
                 "  errors: %lld  rejected: %lld"
                 "  expired: %lld  shed: %lld\n"
                 "  latency_us: p50 %lld  p99 %lld  p999 %lld  max %lld\n",
                 kv.first.c_str(), (long long)st.latency.count(),
                 (long long)st.latency.qps(),
                 (long long)st.concurrency.load(),
                 (long long)st.max_concurrency(),  // 0 = unlimited
                 (long long)st.nerror.load(), (long long)st.nrejected.load(),
                 (long long)st.nexpired.load(), (long long)st.nshed.load(),
                 (long long)st.latency.latency_percentile(0.5),
                 (long long)st.latency.latency_percentile(0.99),
                 (long long)st.latency.latency_percentile(0.999),
                 (long long)st.latency.max_latency());
        res->Append(line);
    }
    res->Append("\n" + stage::DumpText());
}

void HandleVars(Server*, const HttpRequest& req, HttpResponse* res) {
    // /vars?series=<name> -> the variable's 60s/60min/24h ring as JSON.
    bool has_series = false;
    const std::string series = req.QueryParam("series", &has_series);
    if (has_series) {
        const std::string json =
            SeriesCollector::singleton()->SeriesJson(series);
        if (json.empty()) {
            res->status = 404;
            res->set_content_type("text/plain");
            res->Append("no series for: " + series +
                        " (series exist for numeric vars and composite "
                        "fields, e.g. <name>_qps; sampling starts with the "
                        "first server)\n");
            return;
        }
        res->set_content_type("application/json");
        res->Append(json);
        return;
    }
    res->set_content_type("text/plain");
    // /vars/<name> -> one variable. Stays STRICTLY "name : value" — the
    // soaks (and any script) parse this line; trends live in the list
    // view sparklines and /vars?series=.
    if (req.path.size() > 6 && req.path.compare(0, 6, "/vars/") == 0) {
        const std::string name = req.path.substr(6);
        std::string value;
        if (!Variable::describe_exposed(name, &value)) {
            res->status = 404;
            res->Append("no such var: " + name + "\n");
            return;
        }
        res->Append(name + " : " + value + "\n");
        return;
    }
    for (const auto& kv : Variable::dump_exposed()) {
        res->Append(kv.first + " : " + kv.second);
        // Inline sparkline: the last minute of the var's per-second ring.
        const std::string spark =
            SeriesCollector::singleton()->SparklineFor(kv.first);
        if (!spark.empty()) {
            res->Append("  " + spark);
        }
        res->Append("\n");
    }
}

void HandleFlags(Server*, const HttpRequest& req, HttpResponse* res) {
    res->set_content_type("text/plain");
    if (req.path.size() > 7 && req.path.compare(0, 7, "/flags/") == 0) {
        const std::string name = req.path.substr(7);
        FlagBase* f = FindFlag(name);
        if (f == nullptr) {
            res->status = 404;
            res->Append("no such flag: " + name + "\n");
            return;
        }
        bool has_setvalue = false;
        const std::string setvalue = req.QueryParam("setvalue", &has_setvalue);
        if (has_setvalue) {
            if (!SetFlagValue(name, setvalue)) {
                res->status = 400;
                res->Append("bad value for " + name + ": '" + setvalue +
                            "'\n");
                return;
            }
        }
        res->Append(name + " = " + f->GetString() + " (" + f->type() +
                    ")  # " + f->description() + "\n");
        return;
    }
    for (FlagBase* f : ListFlags()) {
        res->Append(std::string(f->name()) + " = " + f->GetString() + " (" +
                    f->type() + ")  # " + f->description() + "\n");
    }
}

// /connections: per-socket I/O attribution (ISSUE 6). in_Bps/out_Bps
// are scrape-to-scrape rates (Socket::ScrapeIoRates — first scrape
// averages since creation); avg/max_batch attribute writev coalescing;
// q_hiwater is the deepest write backlog; crowded counts EOVERCROWDED
// rejections on this connection.
void HandleConnections(Server* server, const HttpRequest&,
                       HttpResponse* res) {
    res->set_content_type("text/plain");
    char line[400];
    res->Append(
        "socket_id            fd    remote              "
        "in_bytes     out_bytes    in_Bps       out_Bps      "
        "wr_batches  avg_batch  max_batch  unwritten  q_hiwater  "
        "crowded  age_s  idle_s\n");
    const int64_t now = monotonic_time_us();
    for (SocketId id : server->acceptor()->connections()) {
        SocketUniquePtr s = SocketUniquePtr::FromId(id);
        if (!s) continue;
        const Socket::IoRates rates = s->ScrapeIoRates(now);
        const int64_t nbatch = s->write_batches();
        const int64_t avg_batch =
            nbatch > 0 ? s->bytes_written() / nbatch : 0;
        snprintf(line, sizeof(line),
                 "%-20llu %-5d %-19s %-12lld %-12lld %-12.0f %-12.0f "
                 "%-11lld %-10lld %-10lld %-10lld %-10lld %-8lld %-6lld "
                 "%lld\n",
                 (unsigned long long)id, s->fd(),
                 endpoint2str(s->remote_side()).c_str(),
                 (long long)s->bytes_read(), (long long)s->bytes_written(),
                 rates.in_bps, rates.out_bps, (long long)nbatch,
                 (long long)avg_batch, (long long)s->max_write_batch_bytes(),
                 (long long)s->unwritten_bytes(),
                 (long long)s->queued_write_highwater(),
                 (long long)s->overcrowded_incidents(),
                 (long long)((now - s->created_us()) / 1000000),
                 (long long)((now - s->last_active_us()) / 1000000));
        res->Append(line);
    }
}

// /chaos: live fault-injection control + observation
// (tnet/fault_injection.h). All mutations go through the chaos_* flags
// (SetFlagValue), so /flags, the command line and this page always
// agree; the flags' on-change hooks re-apply the plan atomically.
//   GET /chaos                     -> current config + injection counters
//   GET /chaos?enable=1&seed=42&plan=drop%3D0.01&peers=ip:port  -> apply
//   GET /chaos?enable=0            -> disable (plan kept)
//   GET /chaos?reset=1             -> zero the injection counters
void HandleChaos(Server*, const HttpRequest& req, HttpResponse* res) {
    res->set_content_type("text/plain");
    // Validate EVERYTHING before mutating ANYTHING: a request rejected
    // with 400 must leave the live configuration untouched (and
    // StringFlag::SetString accepts any string, so plan/peers need
    // explicit validation — Reconfigure would otherwise fail closed
    // silently behind a 200).
    struct Param {
        const char* flag;
        const char* name;
        bool present = false;
        std::string value;
    } params[] = {{"chaos_plan", "plan", false, ""},
                  {"chaos_peers", "peers", false, ""},
                  {"chaos_seed", "seed", false, ""},
                  {"chaos_enabled", "enable", false, ""},
                  // Whole-zone partition (ISSUE 14): any zone name (or
                  // "" to heal) — one request cuts a pod.
                  {"chaos_partition_zone", "partition_zone", false, ""}};
    for (Param& p : params) {
        p.value = req.QueryParam(p.name, &p.present);
    }
    auto reject = [&](const Param& p) {
        res->status = 400;
        res->Append(std::string("bad ") + p.name + ": '" + p.value +
                    "' (nothing applied)\n");
    };
    for (const Param& p : params) {
        if (!p.present) continue;
        bool ok = true;
        if (strcmp(p.name, "plan") == 0) {
            ok = FaultInjection::ValidatePlan(p.value);
        } else if (strcmp(p.name, "peers") == 0) {
            ok = FaultInjection::ValidatePeers(p.value);
        } else if (strcmp(p.name, "seed") == 0) {
            char* end = nullptr;
            (void)strtoll(p.value.c_str(), &end, 10);
            ok = end != p.value.c_str() && *end == '\0';
        } else if (strcmp(p.name, "enable") == 0) {
            ok = p.value == "0" || p.value == "1" || p.value == "true" ||
                 p.value == "false";
        }  // partition_zone: any name is valid; "" heals
        if (!ok) {
            reject(p);
            return;
        }
    }
    // Atomic apply: if chaos is ALREADY running, each per-flag
    // on-change hook would re-enable against a half-applied request
    // (new plan + old peers), so force-disable first and restore the
    // right enable state LAST — serialized against concurrent /chaos
    // requests (two interleaved applies could otherwise commit a mixed
    // config or resurrect a healed plan).
    static std::mutex chaos_apply_mu;
    std::lock_guard<std::mutex> apply_guard(chaos_apply_mu);
    const bool config_change =
        params[0].present || params[1].present || params[2].present;
    const bool was_enabled = FLAGS_chaos_enabled.get();
    if (config_change && was_enabled && !params[3].present) {
        // No explicit enable in the request: keep the previous state.
        params[3].present = true;
        params[3].value = "1";
    }
    if (config_change) SetFlagValue("chaos_enabled", "0");
    for (const Param& p : params) {
        if (p.present && !SetFlagValue(p.flag, p.value)) {
            reject(p);  // unreachable after validation; belt-and-braces
            return;
        }
    }
    if (req.QueryParam("reset") == "1") {
        FaultInjection::ResetCounters();
    }
    res->Append(FaultInjection::DebugString());
}

// /pools: the zero-copy pool data path (ISSUE 10) — live pinned-block
// leases (the crash-safety ledger: a pin with no live RPC is a leak the
// reaper will reclaim), per-class slab occupancy, and every mapped pool
// with its epoch (the stale-descriptor fence). ?format=json is what the
// chaos soak asserts on (pinned back to 0, survivors' epochs intact).
void HandlePools(Server*, const HttpRequest& req, HttpResponse* res) {
    char line[192];
    if (req.QueryParam("format") == "json") {
        res->set_content_type("application/json");
        std::string out;
        // The header's format literals alone exceed 192 chars; its own
        // buffer is sized for them plus eleven 20-digit numbers.
        char head[512];
        snprintf(head, sizeof(head),
                 "{\"pool_id\": %llu, \"pool_epoch\": %llu, "
                 "\"pinned\": %llu, \"pins_total\": %llu, "
                 "\"released\": %llu, \"lease_expired\": %llu, "
                 "\"peer_released\": %llu, \"slab_live\": %zu, "
                 "\"slab_recycled\": %zu, \"pool_resolves\": %llu, "
                 "\"pool_resolve_failures\": %llu, \"classes\": [",
                 (unsigned long long)IciBlockPool::pool_id(),
                 (unsigned long long)IciBlockPool::pool_epoch(),
                 (unsigned long long)block_lease::pinned(),
                 (unsigned long long)block_lease::pins_total(),
                 (unsigned long long)block_lease::released(),
                 (unsigned long long)block_lease::expired_reaped(),
                 (unsigned long long)block_lease::peer_released(),
                 IciBlockPool::slab_allocated(),
                 IciBlockPool::slab_recycled(),
                 (unsigned long long)pool_registry::resolves(),
                 (unsigned long long)pool_registry::resolve_failures());
        out += head;
        for (int c = 0; IciBlockPool::slab_class_bytes(c) != 0; ++c) {
            const auto st = IciBlockPool::slab_class_stat(c);
            snprintf(line, sizeof(line),
                     "%s{\"bytes\": %zu, \"live\": %zu, \"free\": %zu, "
                     "\"carved\": %zu}",
                     c == 0 ? "" : ", ",
                     IciBlockPool::slab_class_bytes(c), st.live,
                     st.freelist, st.carved);
            out += line;
        }
        // Live leases with their direction column (req = client request
        // pin, rsp = server response pin awaiting the client's ack).
        out += "], \"leases\": ";
        out += block_lease::JsonLeases(64);
        // Transport-tier registry + byte attribution (ISSUE 12): one
        // entry per registered endpoint type. Own buffer: the format
        // literals alone approach the shared line[192], so real
        // multi-digit counters would truncate the JSON mid-object.
        out += ", \"transports\": [";
        char tline[512];
        for (int t = 0; t < TransportTierCount(); ++t) {
            const TransportTier* tier = GetTransportTier(t);
            if (tier == nullptr) break;
            snprintf(tline, sizeof(tline),
                     "%s{\"name\": \"%s\", \"descriptor_capable\": %d, "
                     "\"zero_copy\": %d, \"cross_process\": %d, "
                     "\"one_sided\": %d, \"sgl_max\": %u, "
                     "\"in_bytes\": %lld, \"out_bytes\": %lld, "
                     "\"desc_in_bytes\": %lld, \"desc_out_bytes\": %lld, "
                     "\"credit_stalls\": %lld, \"ops\": %lld}",
                     t == 0 ? "" : ", ", tier->name,
                     tier->descriptor_capable ? 1 : 0,
                     tier->zero_copy ? 1 : 0, tier->cross_process ? 1 : 0,
                     tier->one_sided ? 1 : 0, tier->sgl_max,
                     (long long)transport_stats::in_bytes(t),
                     (long long)transport_stats::out_bytes(t),
                     (long long)transport_stats::desc_in_bytes(t),
                     (long long)transport_stats::desc_out_bytes(t),
                     (long long)transport_stats::credit_stalls(t),
                     (long long)transport_stats::ops(t));
            out += tline;
        }
        out += "]}";
        res->Append(out);
        return;
    }
    res->set_content_type("text/plain");
    snprintf(line, sizeof(line), "pool_id %llu\npool_epoch %llu\n",
             (unsigned long long)IciBlockPool::pool_id(),
             (unsigned long long)IciBlockPool::pool_epoch());
    res->Append(line);
    res->Append("-- pinned-block leases --\n");
    res->Append(block_lease::DebugString());
    res->Append("-- slab classes (live/free/carved) --\n");
    for (int c = 0; IciBlockPool::slab_class_bytes(c) != 0; ++c) {
        const auto st = IciBlockPool::slab_class_stat(c);
        snprintf(line, sizeof(line), "class %7zuB live=%zu free=%zu "
                 "carved=%zu\n",
                 IciBlockPool::slab_class_bytes(c), st.live, st.freelist,
                 st.carved);
        res->Append(line);
    }
    res->Append("-- mapped pools (descriptor resolution scope) --\n");
    res->Append(pool_registry::DebugString());
    snprintf(line, sizeof(line), "resolves %llu\nresolve_failures %llu\n",
             (unsigned long long)pool_registry::resolves(),
             (unsigned long long)pool_registry::resolve_failures());
    res->Append(line);
    res->Append("-- transport tiers (capabilities + attribution) --\n");
    res->Append(transport_stats::DebugString());
}

// /tenants: the multi-tenant QoS tier (ISSUE 8) — configured quotas,
// live fair-queue depth, and per-tenant admitted/shed/queued counters
// with the served-latency p99. The same numbers ride /metrics as the
// labelled rpc_tenant_* families; ?format=json is what the overload
// soak asserts on.
// /streams: push-stream tier (ISSUE 17) — the rpc_stream_* counters,
// replay-ring high-water and one row per live server/client stream;
// ?format=json is what the restart soak scrapes.
void HandleStreams(Server*, const HttpRequest& req, HttpResponse* res) {
    if (req.QueryParam("format") == "json") {
        res->set_content_type("application/json");
        res->Append(push_stream::DescribeJson());
        return;
    }
    res->set_content_type("text/plain");
    res->Append(push_stream::DescribeText());
}

// /outliers: the outlier-ejection tier (ISSUE 20) — one section per
// client LB in this process, one row per backend: state, latency EWMA,
// ejection reason + remaining window, probe progress. The grey-node
// soak asserts on ?format=json; the text form is for humans asking
// "why did traffic move off that node".
void HandleOutliers(Server*, const HttpRequest& req, HttpResponse* res) {
    if (req.QueryParam("format") == "json") {
        res->set_content_type("application/json");
        res->Append(outlier::DescribeAllJson());
        return;
    }
    res->set_content_type("text/plain");
    res->Append(outlier::DescribeAll());
}

void HandleTenants(Server* server, const HttpRequest& req,
                   HttpResponse* res) {
    if (req.QueryParam("format") == "json") {
        res->set_content_type("application/json");
        res->Append(server->qos()->DescribeJson());
        return;
    }
    res->set_content_type("text/plain");
    res->Append(server->qos()->DescribeText());
}

// Prometheus text exposition: one registry-wide dump through the
// Variable prometheus hooks — plain numerics as gauges, LatencyRecorders
// as REAL summary families (quantile labels + _sum/_count), labelled
// MultiDimensions with their label sets. Names are sanitized once,
// centrally (tvar/variable.cc SanitizeMetricName); the JSON-description
// substring parser that used to live here is gone.
void HandleMetrics(Server*, const HttpRequest&, HttpResponse* res) {
    res->set_content_type("text/plain; version=0.0.4");
    res->Append(Variable::dump_prometheus());
}

}  // namespace

void AddBuiltinHttpServices(Server* server) {
    // The /pools + /metrics pages report the lease + transport families
    // even on a server that never pinned a block or moved a transport
    // byte (0 is data; absent is not). Same for the collective families
    // (ISSUE 13) — linted 0-valued before the first round.
    block_lease::ExposeVars();
    transport_stats::ExposeVars();
    CollectiveEngine::ExposeVars();
    ExposeZoneLbVars();
    flight::ExposeVars();
    outlier::ExposeVars();
    server->RegisterHttpHandler("/", HandleIndex);
    server->RegisterHttpHandler("/health", HandleHealth);
    server->RegisterHttpHandler("/status", HandleStatus);
    server->RegisterHttpHandler("/vars", HandleVars);
    server->RegisterHttpHandler("/vars/*", HandleVars);
    server->RegisterHttpHandler("/flags", HandleFlags);
    server->RegisterHttpHandler("/flags/*", HandleFlags);
    server->RegisterHttpHandler("/connections", HandleConnections);
    server->RegisterHttpHandler("/rpcz", HandleRpcz);
    server->RegisterHttpHandler("/rpcz/trace/*", HandleRpczTrace);
    server->RegisterHttpHandler("/fibers", HandleFibers);
    server->RegisterHttpHandler("/threads", HandleThreads);
    server->RegisterHttpHandler("/version", HandleVersion);
    server->RegisterHttpHandler("/memory", HandleMemory);
    server->RegisterHttpHandler("/hotspots", HandleHotspotsIndex);
    server->RegisterHttpHandler("/hotspots/cpu", HandleHotspotsCpu);
    server->RegisterHttpHandler("/hotspots/heap", HandleHotspotsHeap);
    server->RegisterHttpHandler("/hotspots/growth", HandleHotspotsGrowth);
    server->RegisterHttpHandler("/loops", HandleLoops);
    server->RegisterHttpHandler("/tenants", HandleTenants);
    server->RegisterHttpHandler("/hotspots/contention",
                                HandleHotspotsContention);
    server->RegisterHttpHandler("/chaos", HandleChaos);
    server->RegisterHttpHandler("/blackbox", HandleBlackbox);
    server->RegisterHttpHandler("/pools", HandlePools);
    server->RegisterHttpHandler("/streams", HandleStreams);
    server->RegisterHttpHandler("/outliers", HandleOutliers);
    server->RegisterHttpHandler("/metrics", HandleMetrics);
}

}  // namespace tpurpc
