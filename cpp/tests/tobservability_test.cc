// Mesh-wide observability (ISSUE 4): SeriesRing rollover under a fake
// clock (append() IS the clock), prometheus summary exposition for
// LatencyRecorder (+ labelled families), the flag->var bridge, and span
// annotation attachment on the shed/cancel/retry paths.
// Performance attribution (ISSUE 6): heap-profiler determinism (fixed
// seed + same allocation sequence -> stable stack set), scheduler
// counters, dispatcher telemetry, and per-tuple series fields of
// labelled families.
// Stage clock (ISSUE 25): StageRecorder is cumulative, two dumps difference
// to exactly what was added between them, its quantile lands within one
// bucket, and a sample costs nanoseconds with every thread writing.
#include <unistd.h>

#include <atomic>
#include <cstdlib>
#include <cstring>
#include <functional>
#include <string>
#include <thread>
#include <vector>

#include "echo.pb.h"
#include "tbase/endpoint.h"
#include "tbase/flags.h"
#include "tbase/heap_profiler.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "tfiber/fiber_sync.h"
#include "tfiber/task_group.h"
#include "tnet/event_dispatcher.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "trpc/server.h"
#include "trpc/server_call.h"
#include "trpc/span.h"
#include "ttest/ttest.h"
#include "tvar/default_variables.h"
#include "tvar/latency_recorder.h"
#include "tvar/multi_dimension.h"
#include "tvar/reducer.h"
#include "tvar/series.h"
#include "tvar/stage_recorder.h"
#include "tvar/variable.h"

using namespace tpurpc;

DECLARE_bool(enable_rpcz);
DECLARE_int64(heap_profiler_sample_bytes);

namespace {

bool WaitUntil(const std::function<bool()>& pred, int64_t timeout_ms) {
    const int64_t deadline = monotonic_time_us() + timeout_ms * 1000;
    while (monotonic_time_us() < deadline) {
        if (pred()) return true;
        usleep(5 * 1000);
    }
    return pred();
}

}  // namespace

// ---------------- SeriesRing: fake-clock rollover ----------------
// append() is the clock (1 call = 1 second), so boundary behavior is
// driven deterministically — no sleeping, no real time.

TEST(SeriesRing, SecondBoundaryRollsIntoMinute) {
    SeriesRing r;
    for (int i = 0; i < 59; ++i) r.append(10.0);
    // 59 ticks: second ring filling, minute ring untouched.
    EXPECT_EQ(r.ticks(), 59);
    std::vector<double> m = r.minutes();
    for (double v : m) EXPECT_EQ(v, 0.0);
    // The 60th tick folds mean(last 60 seconds) into the minute ring.
    r.append(70.0);  // 59x10 + 1x70 -> mean 11
    m = r.minutes();
    EXPECT_EQ(m.back(), 11.0);
    // Second ring keeps rolling: 60 more ticks -> second minute entry.
    for (int i = 0; i < 60; ++i) r.append(5.0);
    m = r.minutes();
    EXPECT_EQ(m.back(), 5.0);
    EXPECT_EQ(m[m.size() - 2], 11.0);
}

TEST(SeriesRing, MinuteBoundaryRollsIntoHour) {
    SeriesRing r;
    // One full hour of ticks at a constant value.
    for (int i = 0; i < 3600; ++i) r.append(3.0);
    std::vector<double> h = r.hours();
    EXPECT_EQ(h.back(), 3.0);
    for (size_t i = 0; i + 1 < h.size(); ++i) EXPECT_EQ(h[i], 0.0);
    // A second hour at a different value: second hour entry, first keeps.
    for (int i = 0; i < 3600; ++i) r.append(9.0);
    h = r.hours();
    EXPECT_EQ(h.back(), 9.0);
    EXPECT_EQ(h[h.size() - 2], 3.0);
}

TEST(SeriesRing, UnrollIsOldestFirstAndZeroPadded) {
    SeriesRing r;
    for (int i = 1; i <= 70; ++i) r.append((double)i);
    const std::vector<double> s = r.seconds();
    ASSERT_EQ((int)s.size(), SeriesRing::kSeconds);
    // 70 ticks through a 60-slot ring: oldest surviving value is 11.
    EXPECT_EQ(s.front(), 11.0);
    EXPECT_EQ(s.back(), 70.0);
    for (size_t i = 1; i < s.size(); ++i) EXPECT_EQ(s[i], s[i - 1] + 1.0);
    // A short series zero-pads at the FRONT (fixed 60-point shape).
    SeriesRing fresh;
    fresh.append(42.0);
    const std::vector<double> f = fresh.seconds();
    ASSERT_EQ((int)f.size(), SeriesRing::kSeconds);
    EXPECT_EQ(f.front(), 0.0);
    EXPECT_EQ(f.back(), 42.0);
}

TEST(SeriesCollector, ExposedVarGrowsARing) {
    Status<int64_t> st(7);
    st.expose("obs_series_probe");
    auto* sc = SeriesCollector::singleton();
    sc->Tick();
    sc->Tick();
    const std::string json = sc->SeriesJson("obs_series_probe");
    ASSERT_TRUE(!json.empty());
    EXPECT_TRUE(json.find("\"name\":\"obs_series_probe\"") !=
                std::string::npos);
    // The per-second ring is always exactly 60 points; the probe's
    // constant value occupies the tail.
    const size_t sec = json.find("\"second\":[");
    ASSERT_TRUE(sec != std::string::npos);
    const size_t end = json.find("]", sec);
    const std::string ring = json.substr(sec + 10, end - sec - 10);
    int commas = 0;
    for (char c : ring) commas += c == ',';
    EXPECT_EQ(commas, 59);
    EXPECT_TRUE(ring.size() >= 2 &&
                ring.compare(ring.size() - 2, 2, ",7") == 0)
        << ring;
    st.hide();
}

// ---------------- heap profiler (ISSUE 6) ----------------

namespace {

__attribute__((noinline)) char* HeapProbeAlloc(size_t n) {
    char* p = new char[n];
    p[0] = 1;  // keep the allocation un-elidable
    return p;
}

// One deterministic round: reset the profiler, run a fixed allocation
// sequence, dump, free. Returns the raw-dump row of the probe site
// (the line whose stack the two rounds must agree on).
__attribute__((noinline)) std::string HeapProbeRound() {
    ResetHeapProfilerForTest();
    std::vector<char*> blocks;
    blocks.reserve(64);
    for (int i = 0; i < 64; ++i) blocks.push_back(HeapProbeAlloc(8191));
    // 64 * 8191 bytes through a 64KiB countdown -> exactly 7 samples of
    // the probe site: the row reads "57337 7 @ <pcs>".
    const std::string raw = HeapProfileRaw(/*growth=*/false);
    for (char* p : blocks) delete[] p;
    const size_t pos = raw.find("57337 7 @");
    if (pos == std::string::npos) return "";
    return raw.substr(pos, raw.find('\n', pos) - pos);
}

}  // namespace

TEST(HeapProfiler, DeterministicSampleSet) {
    if (!HeapProfilerActive() &&
        FLAGS_heap_profiler_sample_bytes.get() > 0) {
        return;  // ASan build: interposition compiled out by design
    }
    const int64_t old = FLAGS_heap_profiler_sample_bytes.get();
    FLAGS_heap_profiler_sample_bytes.set(64 * 1024);
    // Same call site both rounds: the captured stacks must be
    // IDENTICAL — the deterministic-countdown contract.
    std::string row[2];
    for (int i = 0; i < 2; ++i) row[i] = HeapProbeRound();
    EXPECT_TRUE(!row[0].empty());
    EXPECT_EQ(row[0], row[1]);
    FLAGS_heap_profiler_sample_bytes.set(old);
    ResetHeapProfilerForTest();
}

TEST(HeapProfiler, LiveVsGrowthAccounting) {
    if (!HeapProfilerActive() &&
        FLAGS_heap_profiler_sample_bytes.get() > 0) {
        return;  // ASan build
    }
    const int64_t old = FLAGS_heap_profiler_sample_bytes.get();
    FLAGS_heap_profiler_sample_bytes.set(32 * 1024);
    ResetHeapProfilerForTest();
    std::vector<char*> blocks;
    blocks.reserve(32);
    for (int i = 0; i < 32; ++i) blocks.push_back(HeapProbeAlloc(8191));
    HeapProfilerStats live = GetHeapProfilerStats();
    // 32 * 8191 bytes through a 32KiB countdown = 6 deterministic
    // samples of the probe site (one per 5 allocations after the
    // vector's reserve eats into the first window); other threads can
    // only ADD samples, so a floor of 5 is race-proof slack.
    EXPECT_GE(live.live_count, 5);
    EXPECT_GT(live.live_bytes, 0);
    EXPECT_GE(live.growth_count, live.live_count);
    for (char* p : blocks) delete[] p;
    // Frees clear LIVE attribution; growth (churn) is cumulative...
    HeapProfilerStats freed = GetHeapProfilerStats();
    EXPECT_LT(freed.live_count, live.live_count);
    EXPECT_GE(freed.growth_count, live.growth_count);
    // ...until an explicit reset.
    ResetHeapGrowth();
    HeapProfilerStats reset = GetHeapProfilerStats();
    EXPECT_EQ(reset.growth_count, 0);
    const std::string sym = HeapProfileSymbolized(/*growth=*/false, 10);
    EXPECT_TRUE(sym.find("heap profile:") == 0);
    FLAGS_heap_profiler_sample_bytes.set(old);
    ResetHeapProfilerForTest();
}

// ---------------- scheduler + dispatcher telemetry (ISSUE 6) ----------------

namespace {

void* NopFiber(void*) { return nullptr; }

struct UrgentSpawner {
    CountdownEvent done{1};
    static void* Run(void* arg) {
        auto* self = (UrgentSpawner*)arg;
        fiber_t child;
        fiber_start_urgent(&child, nullptr, NopFiber, nullptr);
        fiber_join(child, nullptr);
        self->done.signal();
        return nullptr;
    }
};

}  // namespace

TEST(SchedulerTelemetry, CountersAdvance) {
    TaskControl* c = TaskControl::singleton();
    c->ensure_started();
    const int64_t urgent0 = c->urgent_handoffs();
    // An urgent spawn from ON a worker fiber takes the run-now path.
    UrgentSpawner sp;
    fiber_t tid;
    ASSERT_EQ(
        fiber_start_background(&tid, nullptr, UrgentSpawner::Run, &sp), 0);
    sp.done.wait();
    fiber_join(tid, nullptr);
    EXPECT_GT(c->urgent_handoffs(), urgent0);
    // A burst of background fibers pushes the run queues: the high-water
    // gauge must have seen at least depth 1 somewhere.
    std::vector<fiber_t> tids(256);
    for (auto& t : tids) {
        ASSERT_EQ(fiber_start_background(&t, nullptr, NopFiber, nullptr),
                  0);
    }
    for (auto& t : tids) fiber_join(t, nullptr);
    EXPECT_GE(c->runqueue_highwater(), 1);
    // Counters are visible as labelled families on the registry (the
    // /metrics + /vars?series= surface).
    std::string desc;
    ASSERT_TRUE(Variable::describe_exposed("rpc_scheduler_steals", &desc));
    ASSERT_TRUE(
        Variable::describe_exposed("rpc_scheduler_urgent_handoffs", &desc));
    EXPECT_TRUE(desc.find("pool=\"0\"") != std::string::npos);
}

TEST(DispatcherTelemetry, LoopsCountWakes) {
    // A live echo round-trip guarantees at least one dispatcher exists
    // and delivered events.
    Server server;
    class EchoImpl : public test::EchoService {
    public:
        void Echo(google::protobuf::RpcController*,
                  const test::EchoRequest* request,
                  test::EchoResponse* response,
                  google::protobuf::Closure* done) override {
            response->set_message(request->message());
            done->Run();
        }
    } service;
    ASSERT_EQ(server.AddService(&service), 0);
    EndPoint listen;
    str2endpoint("127.0.0.1:0", &listen);
    ASSERT_EQ(server.Start(listen, nullptr), 0);
    EndPoint ep;
    str2endpoint("127.0.0.1", server.listened_port(), &ep);
    Channel channel;
    ASSERT_EQ(channel.Init(ep, nullptr), 0);
    test::EchoService_Stub stub(&channel);
    Controller cntl;
    test::EchoRequest req;
    req.set_message("loops");
    test::EchoResponse res;
    stub.Echo(&cntl, &req, &res, nullptr);
    ASSERT_TRUE(!cntl.Failed());
    EXPECT_GT(EventDispatcher::TotalEpollWaits(), 0);
    int64_t events = 0;
    EventDispatcher::ForEachLoop(
        [](int, const EventDispatcher::LoopStats& st, void* arg) {
            *(int64_t*)arg += st.events;
        },
        &events);
    EXPECT_GT(events, 0);
    server.Stop();
    server.Join();
}

TEST(MultiDimensionSeries, PerTupleNumericFields) {
    // Labelled families feed the series rings through flattened
    // per-tuple suffixes (ISSUE 6) — the /vars?series=<family>_loop_0
    // contract.
    MultiDimension<Adder<int64_t>> m({"loop"});
    *m.get_stats({"0"}) << 5;
    *m.get_stats({"1"}) << 7;
    const auto fields = m.numeric_fields();
    ASSERT_EQ(fields.size(), (size_t)2);
    bool saw0 = false, saw1 = false;
    for (const auto& f : fields) {
        if (f.first == "_loop_0" && f.second == 5.0) saw0 = true;
        if (f.first == "_loop_1" && f.second == 7.0) saw1 = true;
    }
    EXPECT_TRUE(saw0);
    EXPECT_TRUE(saw1);
}

// ---------------- prometheus exposition ----------------

TEST(Prometheus, LatencyRecorderIsARealSummary) {
    LatencyRecorder lat;
    for (int i = 1; i <= 1000; ++i) lat << i;
    lat.expose("obs_test_latency");
    const std::string dump = Variable::dump_prometheus();
    EXPECT_TRUE(dump.find("# TYPE obs_test_latency summary\n") !=
                std::string::npos);
    EXPECT_TRUE(dump.find("obs_test_latency{quantile=\"0.5\"} ") !=
                std::string::npos);
    EXPECT_TRUE(dump.find("obs_test_latency{quantile=\"0.999\"} ") !=
                std::string::npos);
    EXPECT_TRUE(dump.find("obs_test_latency_count 1000\n") !=
                std::string::npos);
    // _sum is the cumulative sum of recorded values: 1+..+1000.
    EXPECT_TRUE(dump.find("obs_test_latency_sum 500500\n") !=
                std::string::npos);
    // The flat JSON-parsed gauges are gone.
    EXPECT_TRUE(dump.find("obs_test_latency_avg_us") == std::string::npos);
    lat.hide();
}

TEST(Prometheus, PlainCountersStayGauges) {
    Adder<int64_t> a;
    a << 12345678;
    a.expose("obs_test_counter");
    const std::string dump = Variable::dump_prometheus();
    EXPECT_TRUE(dump.find("# TYPE obs_test_counter gauge\n"
                          "obs_test_counter 12345678\n") !=
                std::string::npos);
    a.hide();
}

TEST(Prometheus, LabelledLatencyKeepsLabelsAndSummaryShape) {
    LabelledMetric<LatencyRecorder> lat("obs_req_latency", {"method"});
    *lat.get_stats({"Echo"}) << 100 << 200 << 300;
    *lat.get_stats({"Stats"}) << 50;
    const std::string text = lat.prometheus_text("obs_req_latency");
    EXPECT_TRUE(text.find("# TYPE obs_req_latency summary\n") == 0) << text;
    EXPECT_TRUE(text.find("obs_req_latency{method=\"Echo\","
                          "quantile=\"0.5\"} ") != std::string::npos);
    EXPECT_TRUE(text.find("obs_req_latency_count{method=\"Echo\"} 3") !=
                std::string::npos);
    EXPECT_TRUE(text.find("obs_req_latency_count{method=\"Stats\"} 1") !=
                std::string::npos);
    // Exactly ONE TYPE line for the whole family.
    EXPECT_EQ((int)std::string::npos, (int)text.find("# TYPE", 7));
}

// ---------------- flag -> var bridge ----------------

TEST(FlagBridge, FlagsAreScrapeableVars) {
    ExposeFlagVariables();
    std::string v;
    // Bool flags render 0/1 (scrapeable), reflecting live mutation.
    ASSERT_TRUE(Variable::describe_exposed("flag_enable_rpcz", &v));
    const std::string before = v;
    EXPECT_TRUE(v == "0" || v == "1");
    const bool old = FLAGS_enable_rpcz.get();
    ASSERT_TRUE(SetFlagValue("enable_rpcz", old ? "false" : "true"));
    ASSERT_TRUE(Variable::describe_exposed("flag_enable_rpcz", &v));
    EXPECT_NE(v, before);
    FLAGS_enable_rpcz.set(old);
    // Numeric flags pass through as numbers -> gauges at /metrics.
    ASSERT_TRUE(Variable::describe_exposed("flag_rpcz_stitch_timeout_ms",
                                           &v));
    EXPECT_GT(atoll(v.c_str()), 0);
    const std::string dump = Variable::dump_prometheus();
    EXPECT_TRUE(dump.find("# TYPE flag_rpcz_stitch_timeout_ms gauge") !=
                std::string::npos);
}

// ---------------- span annotations (shed / cancel / retry) ----------------

namespace {

// All notes of the spans matching `trace` currently in the SpanDB.
std::string NotesForTrace(uint64_t trace, Span::Kind* kind_of_first_match,
                          const char* needle) {
    std::string all;
    for (const Span& s : SpanDB::singleton()->Recent(256, trace)) {
        for (const Span::Note& n : s.notes) {
            all += n.text + "\n";
            if (kind_of_first_match != nullptr &&
                strstr(n.text.c_str(), needle) != nullptr) {
                *kind_of_first_match = s.kind;
                kind_of_first_match = nullptr;  // keep the first
            }
        }
    }
    return all;
}

bool TraceHasNote(uint64_t trace, const char* needle) {
    return NotesForTrace(trace, nullptr, needle).find(needle) !=
           std::string::npos;
}

struct RpczOn {
    bool old;
    RpczOn() : old(FLAGS_enable_rpcz.get()) {
        FLAGS_enable_rpcz.set(true);
        // A prior test may have drained the Collector's 1000/s sampling
        // window this very second; idle past it so the first sample()
        // here opens a fresh window and the span is deterministic.
        usleep(1100 * 1000);
    }
    ~RpczOn() { FLAGS_enable_rpcz.set(old); }
};

class ParkUntilCanceledImpl : public test::EchoService {
public:
    void Echo(google::protobuf::RpcController* cntl_base,
              const test::EchoRequest* request, test::EchoResponse* response,
              google::protobuf::Closure* done) override {
        Controller* cntl = static_cast<Controller*>(cntl_base);
        entered.fetch_add(1, std::memory_order_release);
        for (int i = 0; i < 400; ++i) {
            if (cntl->IsCanceled()) break;
            fiber_usleep(5 * 1000);
        }
        response->set_message(request->message());
        done->Run();
    }
    std::atomic<int> entered{0};
};

struct SignalDone : google::protobuf::Closure {
    CountdownEvent ev{1};
    void Run() override { ev.signal(); }
};

}  // namespace

TEST(SpanAnnotations, RetryAndBudgetExhaustionLandOnTheSpan) {
    RpczOn rpcz;
    // Dead port: retryable failures. Budget of 1 -> one re-issue, then
    // the bucket runs dry and the exhaustion is annotated.
    Channel channel;
    ChannelOptions opts;
    opts.timeout_ms = 2000;
    opts.max_retry = 5;
    opts.retry_budget_tokens = 1;
    opts.retry_budget_ratio = 0.0;
    ASSERT_EQ(channel.Init("127.0.0.1:1", &opts), 0);
    test::EchoService_Stub stub(&channel);
    Controller cntl;
    test::EchoRequest req;
    req.set_message("doomed");
    test::EchoResponse res;
    stub.Echo(&cntl, &req, &res, nullptr);
    EXPECT_TRUE(cntl.Failed());
    const uint64_t trace = cntl.trace_id();
    ASSERT_NE(trace, 0u);
    // Spans flow through the Collector's background dispatcher.
    ASSERT_TRUE(WaitUntil(
        [&] { return !SpanDB::singleton()->Recent(8, trace).empty(); },
        3000));
    EXPECT_TRUE(TraceHasNote(trace, "re-issued try 1"))
        << NotesForTrace(trace, nullptr, "");
    EXPECT_TRUE(TraceHasNote(trace, "retry budget exhausted"))
        << NotesForTrace(trace, nullptr, "");
    EXPECT_TRUE(TraceHasNote(trace, "failed: "))
        << NotesForTrace(trace, nullptr, "");
}

TEST(SpanAnnotations, CanceledServerCallAnnotated) {
    RpczOn rpcz;
    ParkUntilCanceledImpl service;
    Server server;
    ASSERT_EQ(server.AddService(&service), 0);
    EndPoint listen;
    str2endpoint("127.0.0.1:0", &listen);
    ASSERT_EQ(server.Start(listen, nullptr), 0);
    EndPoint ep;
    str2endpoint("127.0.0.1", server.listened_port(), &ep);

    Channel channel;
    ChannelOptions opts;
    opts.timeout_ms = 5000;
    ASSERT_EQ(channel.Init(ep, &opts), 0);
    test::EchoService_Stub stub(&channel);
    Controller cntl;
    test::EchoRequest req;
    req.set_message("cancel-me");
    test::EchoResponse res;
    SignalDone done;
    stub.Echo(&cntl, &req, &res, &done);
    const uint64_t trace = cntl.trace_id();
    ASSERT_NE(trace, 0u);
    ASSERT_TRUE(
        WaitUntil([&] { return service.entered.load() >= 1; }, 3000));
    cntl.StartCancel();
    done.ev.wait();
    // Client span: the cancel verdict; server span: the delivered
    // cascade — both under ONE trace id.
    ASSERT_TRUE(WaitUntil(
        [&] {
            return TraceHasNote(trace, "canceled: upstream gave up") &&
                   TraceHasNote(trace, "canceled: wire CANCEL");
        },
        3000));
    Span::Kind kind = Span::CLIENT;
    NotesForTrace(trace, &kind, "canceled: upstream gave up");
    EXPECT_EQ(kind, Span::SERVER);
    server.Stop();
    server.Join();
}

TEST(SpanAnnotations, ExpiredDownstreamShedAnnotatedOnClientSpan) {
    RpczOn rpcz;
    // A healthy echo server...
    ParkUntilCanceledImpl service;  // parks only until canceled/400 loops
    Server server;
    ASSERT_EQ(server.AddService(&service), 0);
    EndPoint listen;
    str2endpoint("127.0.0.1:0", &listen);
    ASSERT_EQ(server.Start(listen, nullptr), 0);
    EndPoint ep;
    str2endpoint("127.0.0.1", server.listened_port(), &ep);

    // ...called under an upstream server context whose budget is ALREADY
    // spent: the downstream request is stamped timeout_ms=0, the server
    // sheds it on arrival, and the verdict is annotated on the client
    // span (the shed hop itself never allocates one — that is the point:
    // the stitched view still shows WHY).
    Controller upstream;
    upstream.InitServerSide(nullptr, EndPoint());
    upstream.set_server_deadline_us(monotonic_time_us() - 50 * 1000);
    uint64_t trace = 0;
    {
        ServerCallScope scope(&upstream);
        Channel channel;
        ChannelOptions opts;
        opts.timeout_ms = 2000;
        opts.max_retry = 0;
        ASSERT_EQ(channel.Init(ep, &opts), 0);
        test::EchoService_Stub stub(&channel);
        Controller cntl;
        test::EchoRequest req;
        req.set_message("stale");
        test::EchoResponse res;
        stub.Echo(&cntl, &req, &res, nullptr);
        EXPECT_TRUE(cntl.Failed());
        trace = cntl.trace_id();
    }
    ASSERT_NE(trace, 0u);
    ASSERT_TRUE(WaitUntil([&] { return TraceHasNote(trace, "failed: "); },
                          3000));
    server.Stop();
    server.Join();
}

// ---------------- stage clock (ISSUE 25) ----------------

namespace {
int64_t thread_cpu_ns() {
    timespec ts;
    clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts);
    return ts.tv_sec * 1000000000L + ts.tv_nsec;
}

// The tests sample the stage the dumps never show, so nothing else in
// this process (earlier tests' RPCs, their fibers) writes beside them.
stage::Snapshot TestStage() { return stage::SnapshotOf(stage::kTestOnly); }
}  // namespace

TEST(StageRecorder, TheTableIsTheEnumAndTheTestStageIsNotPublished) {
    const auto all = stage::SnapshotAll();
    ASSERT_EQ((int)all.size(), (int)stage::kPublished);
    EXPECT_EQ(all[stage::kLinkHandoff].name, "tici.link_handoff");
    EXPECT_EQ(all[stage::kWriteQueue].name, "tnet.write_queue");
    EXPECT_EQ(all[stage::kCallerWake].name, "trpc.caller_wake");
    EXPECT_EQ(all[stage::kWakeToRun].name, "tfiber.wake_to_run");
    EXPECT_EQ(TestStage().name, "test.only");
    EXPECT_TRUE(stage::DumpJson().find("test.only") == std::string::npos);
    // An id outside the table is dropped, not written somewhere.
    stage::Add(stage::kCount, 1);
    stage::Add(-1, 1);
    EXPECT_EQ(stage::SnapshotOf(stage::kCount).count, 0u);
}

TEST(StageRecorder, CumulativeAndTwoDumpsDifferenceToWhatWasAdded) {
    const int id = stage::kTestOnly;
    stage::Add(id, 5);
    stage::Add(id, 700);
    const stage::Snapshot before = TestStage();
    EXPECT_GE(before.count, 2u);
    // Added between the dumps, from this thread and from one that exits
    // (its cell folds into the table): 100 samples of 40 us, one of
    // 9,000,000 (above anything another test adds here).
    for (int i = 0; i < 50; ++i) stage::Add(id, 40);
    std::thread([id] {
        for (int i = 0; i < 50; ++i) stage::Add(id, 40);
        stage::Add(id, 9000000);
    }).join();
    stage::Add(id, -3);  // a negative duration counts as 0
    stage::Snapshot after = TestStage();
    EXPECT_EQ(after.count - before.count, 102u);
    EXPECT_EQ(after.sum_us - before.sum_us, 100 * 40 + 9000000);
    EXPECT_EQ(after.max_us, 9000000);
    after.hist.subtract(before.hist);
    EXPECT_EQ(after.hist.total(), 102u);
    EXPECT_EQ(after.hist.buckets[PercentileHistogram::bucket_of(40)], 100u);
    EXPECT_EQ(after.hist.buckets[PercentileHistogram::bucket_of(9000000)],
              1u);
    EXPECT_EQ(after.hist.buckets[0], 1u);
    // Nothing is ever reset: a third dump still holds the first two.
    EXPECT_EQ(TestStage().count, before.count + 102u);
}

TEST(StageRecorder, QuantileWithinOneBucketAndDumpsAgree) {
    stage::Snapshot before = TestStage();
    for (int v = 1; v <= 10000; ++v) stage::Add(stage::kTestOnly, v);
    stage::Snapshot s = TestStage();
    s.hist.subtract(before.hist);
    for (double q : {0.5, 0.9, 0.99}) {
        const int64_t want = (int64_t)(q * 10000);
        const int got_bucket =
            PercentileHistogram::bucket_of(s.hist.quantile(q));
        const int want_bucket = PercentileHistogram::bucket_of(want);
        EXPECT_LE(std::abs(got_bucket - want_bucket), 1)
            << "q=" << q << " got " << s.hist.quantile(q);
    }
    // The three published forms come from the one table. trpc.caller_wake
    // is sampled only as a synchronous call returns, and none is in
    // flight here: give it the values 1..2000 on top of what earlier
    // tests' calls left, and look for its snapshot in each form.
    for (int v = 1; v <= 2000; ++v) stage::Add(stage::kCallerWake, v);
    const stage::Snapshot w = stage::SnapshotOf(stage::kCallerWake);
    ASSERT_GE(w.count, 2000u);
    const std::string n = std::to_string(w.count);
    const std::string sum = std::to_string(w.sum_us);
    const std::string json = stage::DumpJson();
    EXPECT_TRUE(json.find("\"trpc.caller_wake\":{\"count\":" + n +
                          ",\"sum_us\":" + sum + ",\"max_us\":" +
                          std::to_string(w.max_us) + ",\"buckets\":[[") !=
                std::string::npos)
        << json.substr(0, 400);
    EXPECT_TRUE(stage::DumpText().find("trpc.caller_wake") !=
                std::string::npos);
    std::string prom;
    stage::DumpPrometheus(&prom);
    EXPECT_TRUE(prom.find("# TYPE rpc_stage_us histogram\n") == 0) << prom;
    EXPECT_TRUE(prom.find("rpc_stage_us_bucket{stage=\"trpc.caller_wake\","
                          "le=\"+Inf\"} " + n + "\n") != std::string::npos);
    // le="1023" holds exactly the samples under 1024: bucket indexes
    // below 8 * 10.
    uint64_t under_1024 = 0;
    for (int i = 0; i < 8 * 10; ++i) under_1024 += w.hist.buckets[i];
    EXPECT_GE(under_1024, 1023u);
    EXPECT_TRUE(prom.find("rpc_stage_us_bucket{stage=\"trpc.caller_wake\","
                          "le=\"1023\"} " + std::to_string(under_1024) +
                          "\n") != std::string::npos);
    EXPECT_TRUE(prom.find("rpc_stage_us_count{stage=\"trpc.caller_wake\"} " +
                          n + "\n") != std::string::npos);
    // /metrics carries it through the one render path.
    EXPECT_TRUE(Variable::dump_prometheus().find(
                    "rpc_stage_us_sum{stage=\"trpc.caller_wake\"} " + sum) !=
                std::string::npos);
}

TEST(StageRecorder, SixteenWritersDoNotSerialise) {
    const int id = stage::kTestOnly;
    const uint64_t count_before = TestStage().count;
    constexpr int kThreads = 16;
    constexpr int kPerThread = 2000000;
    std::atomic<bool> go{false};
    std::vector<std::thread> threads;
    std::vector<int64_t> ns((size_t)kThreads, 0);
    for (int t = 0; t < kThreads; ++t) {
        threads.emplace_back([&, t] {
            stage::Add(id, 1);  // the cell exists before the clock starts
            while (!go.load(std::memory_order_acquire)) {
            }
            // The thread's own CPU time: 16 writers on fewer cores spend
            // wall time descheduled, which is not what an add costs.
            const int64_t t0 = thread_cpu_ns();
            for (int i = 0; i < kPerThread; ++i) stage::Add(id, i & 1023);
            ns[(size_t)t] = thread_cpu_ns() - t0;
        });
    }
    go.store(true, std::memory_order_release);
    for (auto& th : threads) th.join();
    int64_t worst = 0;
    for (int64_t v : ns) worst = std::max(worst, v);
    const double ns_per_add = (double)worst / kPerThread;
    // Stamp + add, as a seam pays it, on one thread.
    const int64_t t0 = monotonic_time_ns();
    int64_t last = stage::now_us();
    for (int i = 0; i < 1000000; ++i) {
        const int64_t now = stage::now_us();
        stage::Add(id, now - last);
        last = now;
    }
    const double ns_per_seam = (double)(monotonic_time_ns() - t0) / 1e6;
    printf("StageRecorder: %.1f ns of cpu per add at %d threads (slowest "
           "thread, %d cores), %.1f ns per stamp + add\n",
           ns_per_add, kThreads, (int)std::thread::hardware_concurrency(),
           ns_per_seam);
    EXPECT_EQ(TestStage().count - count_before,
              (uint64_t)kThreads * (kPerThread + 1) + 1000000u);
    // Per-thread cells: under the 30 ns a sample the issue allows shared
    // atomics, with room for a loaded host; the printed number is the
    // record.
    EXPECT_LT(ns_per_add, 60.0);
}
