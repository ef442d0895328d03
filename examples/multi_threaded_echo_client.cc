// N caller fibers issuing sync echoes back-to-back, with live QPS and
// latency percentiles (reference example/multi_threaded_echo_c++).
//   multi_threaded_echo_client HOST:PORT [fibers] [seconds] [payload_bytes]
#include <unistd.h>

#include <atomic>
#include <cstdio>
#include <cstdlib>
#include <vector>

#include "bench_echo.pb.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "tvar/latency_recorder.h"

using namespace tpurpc;

struct Ctx {
    benchpb::EchoService_Stub* stub;
    LatencyRecorder* lat;
    std::atomic<bool>* stop;
    std::atomic<int64_t>* calls;
    size_t payload;
};

static void* Caller(void* arg) {
    auto* c = (Ctx*)arg;
    IOBuf filler;
    filler.append(std::string(c->payload, 'e'));
    while (!c->stop->load(std::memory_order_relaxed)) {
        Controller cntl;
        cntl.set_timeout_ms(2000);
        benchpb::EchoRequest req;
        benchpb::EchoResponse res;
        req.set_send_ts_us(monotonic_time_us());
        cntl.request_attachment().append(filler);
        c->stub->Echo(&cntl, &req, &res, nullptr);
        if (!cntl.Failed()) {
            *c->lat << (monotonic_time_us() - res.send_ts_us());
            c->calls->fetch_add(1, std::memory_order_relaxed);
        }
    }
    return nullptr;
}

int main(int argc, char** argv) {
    if (argc < 2) {
        fprintf(stderr,
                "usage: %s HOST:PORT [fibers] [seconds] [payload_bytes]\n",
                argv[0]);
        return 2;
    }
    const int nfibers = argc > 2 ? atoi(argv[2]) : 16;
    const int seconds = argc > 3 ? atoi(argv[3]) : 5;
    const size_t payload = argc > 4 ? (size_t)atol(argv[4]) : 4096;
    Channel channel;
    ChannelOptions options;
    options.timeout_ms = 2000;
    if (channel.Init(argv[1], &options) != 0) return 1;
    benchpb::EchoService_Stub stub(&channel);
    LatencyRecorder lat;
    std::atomic<bool> stop{false};
    std::atomic<int64_t> calls{0};
    Ctx ctx{&stub, &lat, &stop, &calls, payload};
    std::vector<fiber_t> tids((size_t)nfibers);
    const int64_t t0 = monotonic_time_us();
    for (auto& tid : tids) fiber_start_background(&tid, nullptr, Caller, &ctx);
    for (int s = 0; s < seconds; ++s) {
        usleep(1000 * 1000);
        printf("t=%ds  calls=%lld  p50=%lldus  p99=%lldus\n", s + 1,
               (long long)calls.load(),
               (long long)lat.latency_percentile(0.5),
               (long long)lat.latency_percentile(0.99));
    }
    stop.store(true);
    for (auto tid : tids) fiber_join(tid, nullptr);
    const double secs = (double)(monotonic_time_us() - t0) / 1e6;
    printf("qps=%.0f  (%d fibers, %zuB payload)\n",
           (double)calls.load() / secs, nfibers, payload);
    if (calls.load() == 0) {
        fprintf(stderr, "no call succeeded against %s\n", argv[1]);
        return 1;
    }
    return 0;
}
