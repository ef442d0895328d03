#include "tvar/stage_recorder.h"

#include <atomic>
#include <cinttypes>
#include <cstdio>
#include <mutex>

#include "tvar/variable.h"

namespace tpurpc {
namespace stage {

namespace {

constexpr int kBuckets = PercentileHistogram::kBuckets;

// One (thread, stage). Single writer (the owning thread); readers load
// relaxed. A reader racing a sample may see its count without its bucket:
// off by one sample, gone at the next read.
struct Cell {
    std::atomic<uint64_t> count{0};
    std::atomic<int64_t> sum_us{0};
    std::atomic<int64_t> max_us{0};
    std::atomic<uint64_t> buckets[kBuckets] = {};
};

struct ThreadCells {
    std::atomic<Cell*> cells[kCount] = {};
};

void Fold(const Cell& c, Snapshot* into) {
    into->count += c.count.load(std::memory_order_relaxed);
    into->sum_us += c.sum_us.load(std::memory_order_relaxed);
    const int64_t mx = c.max_us.load(std::memory_order_relaxed);
    if (mx > into->max_us) into->max_us = mx;
    for (int i = 0; i < kBuckets; ++i) {
        into->hist.buckets[i] += c.buckets[i].load(std::memory_order_relaxed);
    }
}

// Exposed as `rpc_stage_us` so the one /metrics render path
// (Variable::dump_prometheus) carries the histogram family; its /vars
// line is text, so the series sampler leaves it alone.
class StageTableVar : public Variable {
public:
    std::string get_description() const override {
        return "stages=" + std::to_string((int)kPublished) +
               " (cumulative; /status shows them)";
    }
    std::vector<std::pair<std::string, double>> numeric_fields()
        const override {
        return {};
    }
    void prometheus_text(const std::string&,
                         std::string* out) const override {
        DumpPrometheus(out);
    }
};

const char* const kNames[kCount] = {
    "tici.link_handoff",  "tnet.consume_to_cut", "tfiber.dispatch_to_handler",
    "trpc.handler",       "trpc.respond",        "tnet.write_queue",
    "trpc.issue",         "trpc.match",          "trpc.caller_wake",
    "tfiber.wake_to_run", "tdev.take_wait",      "tdev.reply",
    "test.only",
};

// Immortal: worker threads sample (and exit) after static destruction.
struct Table {
    std::mutex mu;  // live, residual
    std::vector<ThreadCells*> live;
    Snapshot residual[kCount];  // what exited threads had recorded

    Table() { (new StageTableVar)->expose("rpc_stage_us"); }
};

Table& table() {
    static Table* t = new Table;
    return *t;
}

thread_local ThreadCells* t_cells = nullptr;

// Folds this thread's cells into the residual when the thread exits.
struct Retirer {
    ThreadCells* cells = nullptr;
    ~Retirer() {
        if (cells == nullptr) return;
        Table& t = table();
        std::lock_guard<std::mutex> g(t.mu);
        for (size_t i = 0; i < t.live.size(); ++i) {
            if (t.live[i] == cells) {
                t.live[i] = t.live.back();
                t.live.pop_back();
                break;
            }
        }
        for (int s = 0; s < kCount; ++s) {
            Cell* c = cells->cells[s].load(std::memory_order_relaxed);
            if (c == nullptr) continue;
            Fold(*c, &t.residual[s]);
            delete c;
        }
        delete cells;
        t_cells = nullptr;
    }
};

Cell* SlowCell(int stage) {
    Table& t = table();
    if (t_cells == nullptr) {
        thread_local Retirer retirer;
        auto* tc = new ThreadCells;
        {
            std::lock_guard<std::mutex> g(t.mu);
            t.live.push_back(tc);
        }
        retirer.cells = tc;
        t_cells = tc;
    }
    Cell* c = new Cell;
    t_cells->cells[stage].store(c, std::memory_order_release);
    return c;
}

}  // namespace

void Add(int stage, int64_t us) {
    if ((unsigned)stage >= (unsigned)kCount) return;
    if (us < 0) us = 0;
    ThreadCells* tc = t_cells;
    Cell* c = tc != nullptr
                  ? tc->cells[stage].load(std::memory_order_relaxed)
                  : nullptr;
    if (__builtin_expect(c == nullptr, 0)) c = SlowCell(stage);
    c->count.store(c->count.load(std::memory_order_relaxed) + 1,
                   std::memory_order_relaxed);
    c->sum_us.store(c->sum_us.load(std::memory_order_relaxed) + us,
                    std::memory_order_relaxed);
    if (us > c->max_us.load(std::memory_order_relaxed)) {
        c->max_us.store(us, std::memory_order_relaxed);
    }
    std::atomic<uint64_t>& b = c->buckets[PercentileHistogram::bucket_of(us)];
    b.store(b.load(std::memory_order_relaxed) + 1, std::memory_order_relaxed);
}

Snapshot SnapshotOf(int stage) {
    if ((unsigned)stage >= (unsigned)kCount) return Snapshot();
    Table& t = table();
    std::lock_guard<std::mutex> g(t.mu);
    Snapshot out = t.residual[stage];
    out.name = kNames[stage];
    for (ThreadCells* tc : t.live) {
        const Cell* c = tc->cells[stage].load(std::memory_order_acquire);
        if (c != nullptr) Fold(*c, &out);
    }
    return out;
}

std::vector<Snapshot> SnapshotAll() {
    std::vector<Snapshot> out;
    for (int s = 0; s < kPublished; ++s) out.push_back(SnapshotOf(s));
    return out;
}

std::string DumpJson() {
    std::string out = "{";
    char buf[96];
    bool first = true;
    for (const Snapshot& s : SnapshotAll()) {
        if (!first) out += ",";
        first = false;
        snprintf(buf, sizeof(buf),
                 "\":{\"count\":%" PRIu64 ",\"sum_us\":%" PRId64
                 ",\"max_us\":%" PRId64 ",\"buckets\":[",
                 s.count, s.sum_us, s.max_us);
        out += "\"" + s.name + buf;
        bool first_bucket = true;
        for (int i = 0; i < kBuckets; ++i) {
            if (s.hist.buckets[i] == 0) continue;
            snprintf(buf, sizeof(buf), "%s[%d,%" PRIu64 "]",
                     first_bucket ? "" : ",", i, s.hist.buckets[i]);
            first_bucket = false;
            out += buf;
        }
        out += "]}";
    }
    out += "}";
    return out;
}

std::string DumpText() {
    std::string out =
        "stages (us, cumulative since start)\n"
        "  stage                             count     mean      p50"
        "      p99        max\n";
    char line[192];
    for (const Snapshot& s : SnapshotAll()) {
        snprintf(line, sizeof(line),
                 "  %-28s %10" PRIu64 " %8.1f %8" PRId64 " %8" PRId64
                 " %10" PRId64 "\n",
                 s.name.c_str(), s.count,
                 s.count != 0 ? (double)s.sum_us / (double)s.count : 0.0,
                 s.hist.quantile(0.5), s.hist.quantile(0.99), s.max_us);
        out += line;
    }
    return out;
}

void DumpPrometheus(std::string* out) {
    *out += "# TYPE rpc_stage_us histogram\n";
    char buf[320];
    for (const Snapshot& s : SnapshotAll()) {
        // le at the octave bounds 7, 15, 31, ...: bucket indexes below
        // 8*k hold exactly the values under 2^k (k >= 3; 0..7 sit in
        // the first eight).
        uint64_t seen = 0;
        int next = 0;
        for (int k = 3; k < PercentileHistogram::kOctaves; ++k) {
            for (; next < PercentileHistogram::kSub * k; ++next) {
                seen += s.hist.buckets[next];
            }
            const uint64_t le = ((uint64_t)1 << k) - 1;
            snprintf(buf, sizeof(buf),
                     "rpc_stage_us_bucket{stage=\"%s\",le=\"%" PRIu64
                     "\"} %" PRIu64 "\n",
                     s.name.c_str(), le, seen);
            *out += buf;
        }
        for (; next < kBuckets; ++next) seen += s.hist.buckets[next];
        snprintf(buf, sizeof(buf),
                 "rpc_stage_us_bucket{stage=\"%s\",le=\"+Inf\"} %" PRIu64
                 "\nrpc_stage_us_sum{stage=\"%s\"} %" PRId64
                 "\nrpc_stage_us_count{stage=\"%s\"} %" PRIu64 "\n",
                 s.name.c_str(), seen, s.name.c_str(), s.sum_us,
                 s.name.c_str(), s.count);
        *out += buf;
    }
}

}  // namespace stage
}  // namespace tpurpc
