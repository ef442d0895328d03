// Continuous micro-batching inference server (ISSUE 17): the serve
// plane an LLM-style token generator actually needs, built entirely
// from this framework's pieces.
//
//   * Requests arrive as ordinary RPCs whose payload "stream:N:key"
//     asks for an N-token response; admission is the server's normal
//     QoS tier (work-priced cost model + per-tenant quotas, ISSUE 15 —
//     enable with --tenant_quotas), so a flooding bronze tenant sheds
//     BEFORE it ever reaches the batch.
//   * Admitted sequences join a CONTINUOUS micro-batch: one device
//     step per tick serves one token to EVERY batch member (the step
//     cost amortizes across the batch — that is the whole win), and
//     membership is recomputed BETWEEN steps: finished sequences leave,
//     waiting ones join immediately — no batch-boundary barriers.
//     Membership is priority-ordered with an optional per-tenant slot
//     cap (--tenant_batch_cap), so gold keeps its seat while bronze
//     floods.
//   * Tokens leave through the resumable server-push stream tier
//     (trpc/stream.h): per-sequence emitter fibers park on receiver
//     credits, and a consumer that stops reading gets its SLOT
//     preempted (not its memory grown) until it catches up. Token
//     content is deterministic in (key, index), so a restarted process
//     regenerates a resumed stream exactly.
//
// Drive it with: rpc_press --stream_tokens=N [--tenants=...] and
// SIGTERM it mid-stream — clients resume, token streams stay
// seq-contiguous.
//
//   infer_server [port] [--step_us N] [--max_batch N]
//                [--tenant_batch_cap N] [--unbatched]
//                [--tenant_quotas spec] [--graceful]
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <cstdio>
#include <cstring>
#include <deque>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

#include "bench_echo.pb.h"
#include "tbase/errno.h"
#include "tbase/flags.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "trpc/controller.h"
#include "trpc/server.h"
#include "trpc/stream.h"
#include "tvar/latency_recorder.h"
#include "tvar/reducer.h"

using namespace tpurpc;

namespace {

int64_t g_step_us = 2000;      // one device step (per BATCH, not token)
int g_max_batch = 8;           // micro-batch width
int g_tenant_batch_cap = 0;    // max slots one tenant holds (0 = none)
bool g_unbatched = false;      // serve one sequence per step (baseline)

// Grant run-ahead before a sequence counts as consumer-stalled. The
// emitter drains grants asynchronously (its own fiber, possibly parked
// on receiver credits) — a budget of a few tokens separates ordinary
// fiber-scheduling lag from a consumer that stopped reading. Memory
// stays bounded either way: unemitted grants are counters, and emitted
// chunks are capped by the rx window + replay ring.
constexpr uint64_t kGrantRunAhead = 4;

// One admitted generation request. The scheduler GRANTS tokens (one
// per step while the sequence holds a batch slot); the emitter fiber
// converts grants into stream Writes, parking on receiver credits —
// so a stalled consumer parks its emitter, never the scheduler.
struct Seq {
    push_stream::StreamWriter w;
    std::string key;
    std::string tenant;
    int priority = 4;
    uint64_t total = 0;
    std::atomic<uint64_t> granted{0};
    std::atomic<uint64_t> emitted{0};
    std::atomic<bool> failed{false};
    fiber_t tid = 0;
};

LazyAdder g_adm("infer_admitted");      // sequences admitted to the pool
LazyAdder g_steps("infer_steps");       // device steps executed
LazyAdder g_tokens("infer_tokens");     // tokens granted (== generated)
LazyAdder g_preempted("infer_preempted");  // slot losses to backpressure

// Batch width per step (a "latency" of N = N members). Leaked + built
// on first use: the tvar registry must not run at static-init time.
LatencyRecorder& BatchSizeVar() {
    static LatencyRecorder* r = [] {
        auto* v = new LatencyRecorder;
        v->expose("infer_batch_size");
        return v;
    }();
    return *r;
}

void* EmitterMain(void* arg) {
    auto* s = (Seq*)arg;
    while (!s->failed.load(std::memory_order_acquire)) {
        const uint64_t done = s->emitted.load(std::memory_order_relaxed);
        if (done >= s->total) break;
        if (done >= s->granted.load(std::memory_order_acquire)) {
            fiber_usleep(500);  // scheduler owns the pace
            continue;
        }
        const uint64_t i = done + 1;
        char tok[96];
        snprintf(tok, sizeof(tok), "tok:%s:%llu", s->key.c_str(),
                 (unsigned long long)i);
        // Parks on receiver credits / rebind; deterministic content
        // means a post-restart resume regenerates the same stream.
        if (s->w.Write(tok, i == s->total) != 0) {
            s->failed.store(true, std::memory_order_release);
            break;
        }
        s->emitted.store(i, std::memory_order_release);
    }
    return nullptr;
}

// The continuous micro-batching scheduler: one fiber, one step per
// tick. Between steps it re-forms the batch from the live pool —
// priority first, stalled consumers preempted, per-tenant slot cap.
class BatchScheduler {
public:
    void Admit(std::unique_ptr<Seq> s) {
        Seq* raw = s.get();
        if (fiber_start_background(&raw->tid, nullptr, EmitterMain, raw) !=
            0) {
            raw->w.Abort(TERR_INTERNAL);
            return;
        }
        std::lock_guard<std::mutex> lk(mu_);
        pool_.push_back(std::move(s));
        *g_adm << 1;
    }

    void Start() {
        fiber_start_background(&tid_, nullptr, &BatchScheduler::Main, this);
    }

    void Stop() {
        stop_.store(true, std::memory_order_release);
        if (tid_ != 0) fiber_join(tid_, nullptr);
    }

private:
    static void* Main(void* arg) {
        ((BatchScheduler*)arg)->Loop();
        return nullptr;
    }

    void Loop() {
        while (!stop_.load(std::memory_order_acquire)) {
            std::vector<Seq*> batch;
            {
                std::lock_guard<std::mutex> lk(mu_);
                Reap();
                FormBatch(&batch);
            }
            if (batch.empty()) {
                fiber_usleep(200);
                continue;
            }
            // THE device step: one fixed cost serves every member —
            // batched tokens/s scales with width, unbatched doesn't.
            fiber_usleep(g_step_us);
            *g_steps << 1;
            BatchSizeVar() << (int64_t)batch.size();
            for (Seq* s : batch) {
                s->granted.fetch_add(1, std::memory_order_release);
                *g_tokens << 1;
            }
        }
        std::lock_guard<std::mutex> lk(mu_);
        for (auto& s : pool_) {
            s->failed.store(true, std::memory_order_release);
            s->w.Abort(TERR_CLOSE);
        }
        Reap();
    }

    // Drop finished/failed sequences (join their emitters). mu_ held.
    void Reap() {
        for (size_t i = 0; i < pool_.size();) {
            Seq* s = pool_[i].get();
            const bool done =
                s->emitted.load(std::memory_order_acquire) >= s->total &&
                s->granted.load(std::memory_order_acquire) >= s->total;
            if (done || s->failed.load(std::memory_order_acquire)) {
                fiber_join(s->tid, nullptr);
                pool_.erase(pool_.begin() + (long)i);
            } else {
                ++i;
            }
        }
    }

    // Membership for the NEXT step. mu_ held. Priority-descending
    // stable order; a sequence whose grants ran kGrantRunAhead past
    // its emitter (consumer parked on credits) is skipped — preemption,
    // not buffering; a tenant past --tenant_batch_cap yields its extra
    // seats.
    void FormBatch(std::vector<Seq*>* batch) {
        std::vector<Seq*> order;
        order.reserve(pool_.size());
        for (auto& s : pool_) order.push_back(s.get());
        std::stable_sort(order.begin(), order.end(),
                         [](const Seq* a, const Seq* b) {
                             return a->priority > b->priority;
                         });
        const size_t width = g_unbatched ? 1 : (size_t)g_max_batch;
        std::vector<std::pair<std::string, int>> seats;
        for (Seq* s : order) {
            if (batch->size() >= width) break;
            if (s->granted.load(std::memory_order_acquire) >=
                s->emitted.load(std::memory_order_acquire) +
                    kGrantRunAhead) {
                *g_preempted << 1;  // consumer behind: slot goes elsewhere
                continue;
            }
            if (g_tenant_batch_cap > 0) {
                int* held = nullptr;
                for (auto& kv : seats) {
                    if (kv.first == s->tenant) held = &kv.second;
                }
                if (held == nullptr) {
                    seats.emplace_back(s->tenant, 0);
                    held = &seats.back().second;
                }
                if (*held >= g_tenant_batch_cap) continue;
                ++*held;
            }
            batch->push_back(s);
        }
    }

    std::mutex mu_;
    std::vector<std::unique_ptr<Seq>> pool_;
    std::atomic<bool> stop_{false};
    fiber_t tid_ = 0;
};

BatchScheduler g_sched;

class InferServiceImpl : public benchpb::EchoService {
public:
    void Echo(google::protobuf::RpcController* cntl_base,
              const benchpb::EchoRequest* request,
              benchpb::EchoResponse* response,
              google::protobuf::Closure* done) override {
        Controller* cntl = static_cast<Controller*>(cntl_base);
        response->set_send_ts_us(request->send_ts_us());
        unsigned long long n = 0;
        char key[64] = {0};
        if (!request->has_payload() ||
            sscanf(request->payload().c_str(), "stream:%llu:%63s", &n,
                   key) != 2 ||
            n == 0 || n > (1ull << 20)) {
            cntl->SetFailed(TERR_REQUEST,
                            "expected payload stream:<tokens>:<key>");
            done->Run();
            return;
        }
        push_stream::StreamWriter w = cntl->accept_stream();
        if (!w.valid()) {
            cntl->SetFailed(TERR_REQUEST, "not a push-stream open");
            done->Run();
            return;
        }
        // Same-process resume: the original emitter still owns the
        // stream; ring replay + the rebind cover continuation.
        if (!w.resumed_in_place()) {
            auto s = std::make_unique<Seq>();
            s->w = w;
            s->key = key;
            s->tenant = cntl->tenant();
            s->priority = cntl->priority();
            s->total = n;
            // Post-restart resume: regenerate from the client's floor.
            s->granted.store(w.resume_from(), std::memory_order_relaxed);
            s->emitted.store(w.resume_from(), std::memory_order_relaxed);
            g_sched.Admit(std::move(s));
        }
        done->Run();
    }
};

}  // namespace

int main(int argc, char** argv) {
    int port = 8020;
    for (int i = 1; i < argc; ++i) {
        if (strcmp(argv[i], "--step_us") == 0 && i + 1 < argc) {
            g_step_us = atoll(argv[++i]);
        } else if (strcmp(argv[i], "--max_batch") == 0 && i + 1 < argc) {
            g_max_batch = atoi(argv[++i]);
        } else if (strcmp(argv[i], "--tenant_batch_cap") == 0 &&
                   i + 1 < argc) {
            g_tenant_batch_cap = atoi(argv[++i]);
        } else if (strcmp(argv[i], "--unbatched") == 0) {
            g_unbatched = true;
        } else if (strcmp(argv[i], "--tenant_quotas") == 0 &&
                   i + 1 < argc) {
            // Work-priced admission (ISSUE 15) in front of the batch.
            SetFlagValue("rpc_tenant_quotas", argv[++i]);
        } else if (strcmp(argv[i], "--graceful") == 0) {
            SetFlagValue("graceful_quit_on_sigterm", "true");
        } else {
            port = atoi(argv[i]);
        }
    }
    BatchSizeVar();  // eager expose: scrapes see the var before traffic
    InferServiceImpl service;
    Server server;
    if (server.AddService(&service) != 0) return 1;
    if (server.Start(port, nullptr) != 0) {
        fprintf(stderr, "failed to listen on %d\n", port);
        return 1;
    }
    g_sched.Start();
    // Scripted-boot handshake (the same contract as mesh_node, which
    // the soaks use).
    printf("READY %d\n", server.listened_port());
    fflush(stdout);
    printf("InferServer on :%d — step %lldus, batch %d%s; try\n"
           "  tools/rpc_press --server=127.0.0.1:%d --stream_tokens=64 "
           "--qps=4 --duration_s=5\n"
           "  curl http://127.0.0.1:%d/streams\n",
           server.listened_port(), (long long)g_step_us, g_max_batch,
           g_unbatched ? " (UNBATCHED baseline)" : "",
           server.listened_port(), server.listened_port());
    server.RunUntilAskedToQuit(/*max_drain_ms=*/5000);
    g_sched.Stop();
    return 0;
}
