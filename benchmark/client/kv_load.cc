// The benchmark's own load generator for the cell of `kv_handoff_1chip`: N
// caller fibers, each one prefill stream: a SYNC kvpb.Cache/Put of layer 0,
// 1, ... L-1 of a session back to back, then the next session (a closed
// loop: in-flight work is bounded by N, so a stall makes replies late and can
// make none fail), for a fixed wall-clock window.
//
//   kv_load --port P --callers N --bytes B --layers L --seed S --seconds T
//           --sample-out FILE [--warm-ms W] [--timeout-ms D]
//
// Protocol with the parent (benchmark/drivers/kvcache.py), as
// tensor_load.cc's with one step more:
//   1. connect (Channel::InitIci: TCP handshake, then the shm link), warm
//      every caller for W ms, print "READY\n";
//   2. wait for a line on stdin (the parent brackets the window with its
//      counter scrapes and the profiler), then run the window;
//   3. every Put STARTED inside the window is waited for (the per-call
//      deadline bounds that drain) and its reply's word compared with the
//      word computed HERE from what was sent; latencies of all of them go to
//      FILE as raw little-endian uint64 nanoseconds; one JSON line goes to
//      stdout. It carries every session any caller was acknowledged for
//      since the connection (warm-up included: they took pool slots) with
//      the admission number the service gave it, and, as tensor_load.cc's,
//      THIS process's stage table and `*_timeouts` / `*_found_work` counters
//      after the warm-up (`client_before`) and after the drain
//      (`client_after`), both outside `window_s`;
//   4. wait for a second line, "READBACK <session>" (the parent has scraped
//      the window's end by now, so what follows is in no timed number): Get
//      G seeded layers of each caller's last 2 completed sessions and
//      compare each byte for byte with what was put; Get layer 0 of
//      <session>, which the parent's reference says was evicted (0: none
//      was), and report the error code; one more JSON line.
//
// Session n (from 1) of caller c: id = (mix64(seed, c, n) & 0xFFFFFF) << 40
// | c << 32 | n, so no two are equal. The Put of layer l, as operation q
// (from 1, warm-up included) of caller c, B bytes, B a multiple of 8 and at
// least 24:
//   bytes [0,8)   little-endian session id
//   bytes [8,12)  little-endian layer
//   bytes [12,16) little-endian (c << 24) | (q & 0xFFFFFF)
//   bytes [16,B)  little-endian words mix64(seed, c, j), see PayloadWord
//                 (benchmark/payload.py makes the same bytes in numpy): one
//                 base buffer a caller, shared by all its requests.
// Reply: word = sum over j of x[j] * (2j + 1), wrapping, over the request as
// little-endian uint32 words x (benchmark/kv_reference.py writes the same in
// numpy, and the parent holds this file's digests to it).
#include <sys/prctl.h>
#include <sys/resource.h>
#include <signal.h>
#include <time.h>
#include <unistd.h>
#include <zlib.h>

#include <algorithm>
#include <atomic>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <string>
#include <vector>

#include "kvcache.pb.h"
#include "tbase/endpoint.h"
#include "tbase/flags.h"
#include "tbase/iobuf.h"
#include "tfiber/fiber.h"
#include "tici/block_pool.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "tvar/stage_recorder.h"
#include "tvar/variable.h"

using namespace tpurpc;

DECLARE_int32(socket_send_buffer_size);
DECLARE_int32(socket_recv_buffer_size);

namespace {

constexpr size_t kStamp = 16;       // bytes [0,16) of a request
constexpr int kReadbackSessions = 2;  // of each caller, its last completed
constexpr int kReadbackLayers = 8;    // of each of them, seeded

int64_t NowNs() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (int64_t)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

uint64_t Mix64(uint64_t z) {
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
}

// Word j of stream `stream` under `seed` (counter-based, so numpy can make
// the same words in one vector expression).
uint64_t PayloadWord(uint64_t seed, uint64_t stream, uint64_t j) {
    return Mix64(seed * 0x9E3779B97F4A7C15ULL +
                 stream * 0xD1B54A32D192ED03ULL +
                 (j + 1) * 0x9E3779B97F4A7C15ULL);
}

std::string MakeBody(uint64_t seed, uint64_t stream, size_t nbytes) {
    std::string out(nbytes, '\0');
    for (size_t off = 0, j = 0; off < nbytes; off += 8, ++j) {
        const uint64_t w = PayloadWord(seed, stream, j);
        memcpy(&out[off], &w, std::min<size_t>(8, nbytes - off));
    }
    return out;
}

// Stream `callers + c` names caller c's sessions and its readback layers, so
// that they share nothing with its payload (stream c).
uint64_t SessionId(uint64_t seed, uint64_t callers, uint64_t c, uint64_t n) {
    return ((PayloadWord(seed, callers + c, n) & 0xFFFFFF) << 40) |
           (c << 32) | n;
}

struct Stamp {
    uint64_t session;
    uint32_t layer;
    uint32_t caller_seq;
};
static_assert(sizeof(Stamp) == kStamp, "a stamp is 16 bytes");

Stamp MakeStamp(uint64_t session, uint32_t layer, uint64_t caller,
                uint64_t seq) {
    return Stamp{session, layer,
                 (uint32_t)((caller << 24) | (seq & 0xFFFFFF))};
}

// The stamp's part of the integrity word: its four words at j = 0..3.
uint32_t StampWord(const Stamp& s) {
    uint32_t w[4];
    memcpy(w, &s, kStamp);
    return w[0] * 1u + w[1] * 3u + w[2] * 5u + w[3] * 7u;
}

// got == stamp(16) + body, compared in place block by block.
bool SameBytes(const IOBuf& got, const Stamp& stamp, const std::string& body) {
    const size_t total = kStamp + body.size();
    if (got.size() != total) return false;
    size_t pos = 0;
    for (size_t i = 0; i < got.backing_block_num(); ++i) {
        size_t len = 0;
        const char* p = got.backing_block_data(i, &len);
        while (len > 0) {
            const char* want;
            size_t room;
            if (pos < kStamp) {
                want = (const char*)&stamp + pos, room = kStamp - pos;
            } else {
                want = body.data() + (pos - kStamp), room = total - pos;
            }
            const size_t n = std::min(len, room);
            if (memcmp(p, want, n) != 0) return false;
            p += n, len -= n, pos += n;
        }
    }
    return pos == total;
}

struct Shared {
    kvpb::Cache_Stub* stub;
    int64_t timeout_ms;
    uint64_t seed;
    uint64_t callers;
    uint32_t layers;
    int64_t t_start_ns;  // the window's start (per-second counts)
    int64_t t_end_ns;    // no operation starts at or after this
    bool record;       // false during the warm-up
};

// A session some Put of which was acknowledged.
struct Session {
    uint64_t id;
    uint64_t admitted;   // as the service said, in each of its replies
    uint64_t first_seq;  // the caller's operation that put its layer 0
    uint32_t acked;      // layers acknowledged with the right word
    bool admitted_moved;  // two replies of it named different admissions
};

struct Caller {
    Shared* shared = nullptr;
    uint64_t idx = 0;
    std::string body;  // bytes [16,B) of every request
    IOBuf body_buf;    // the same, appended by reference to each request
    uint32_t body_word = 0;  // the body's part of the integrity word
    uint64_t seq = 0;        // operations started
    uint64_t nsession = 0;   // sessions started
    uint32_t next_layer = 0;  // of the current session; 0: start a new one
    std::vector<Session> sessions;
    int64_t attempted = 0, ok = 0, rpc_failed = 0, mismatched = 0;
    int64_t last_done_ns = 0;
    // The last Put acknowledged: what it was and the word expected for it.
    uint64_t last_session = 0, last_seq = 0;
    uint32_t last_layer = 0, last_word = 0;
    std::vector<uint64_t> lat_ns;
    std::vector<int64_t> per_s;  // completions in second i of the window
    std::map<int, int64_t> errors;
};

void* CallerLoop(void* arg) {
    Caller* c = (Caller*)arg;
    Shared* s = c->shared;
    for (;;) {
        const int64_t t0 = NowNs();
        if (t0 >= s->t_end_ns) break;
        if (c->next_layer == 0) {
            ++c->nsession;
            c->sessions.push_back(Session{
                SessionId(s->seed, s->callers, c->idx, c->nsession), 0,
                c->seq + 1, 0, false});
        }
        Session& session = c->sessions.back();
        const uint32_t layer = c->next_layer;
        c->next_layer = (layer + 1) % s->layers;
        const Stamp stamp = MakeStamp(session.id, layer, c->idx, ++c->seq);
        Controller cntl;
        cntl.set_timeout_ms(s->timeout_ms);
        cntl.set_max_retry(0);
        kvpb::PutRequest req;
        kvpb::PutResponse res;
        req.set_session(session.id);
        req.set_layer(layer);
        cntl.request_attachment().append(&stamp, kStamp);
        cntl.request_attachment().append(c->body_buf);
        s->stub->Put(&cntl, &req, &res, nullptr);
        const int64_t t1 = NowNs();
        const uint32_t word = c->body_word + StampWord(stamp);
        const bool good = !cntl.Failed() && res.word() == word;
        if (good) {
            if (session.acked++ == 0) {
                session.admitted = res.admitted();
            } else if (session.admitted != res.admitted()) {
                session.admitted_moved = true;
            }
            c->last_session = session.id, c->last_layer = layer;
            c->last_seq = c->seq, c->last_word = word;
        }
        if (!s->record) continue;
        ++c->attempted;
        c->last_done_ns = t1;
        c->lat_ns.push_back((uint64_t)(t1 - t0));
        const size_t sec = (size_t)((t1 - s->t_start_ns) / 1000000000LL);
        if (sec >= c->per_s.size()) c->per_s.resize(sec + 1, 0);
        ++c->per_s[sec];
        if (cntl.Failed()) {
            ++c->rpc_failed;
            if (++c->errors[cntl.ErrorCode()] == 1) {
                fprintf(stderr, "kv_load: caller %llu rpc failed (%d): %s\n",
                        (unsigned long long)c->idx, cntl.ErrorCode(),
                        cntl.ErrorText().c_str());
            }
        } else if (!good) {
            ++c->mismatched;
        } else {
            ++c->ok;
        }
    }
    return nullptr;
}

void RunCallers(std::vector<Caller>& callers) {
    std::vector<fiber_t> tids(callers.size());
    for (size_t i = 0; i < callers.size(); ++i) {
        fiber_start_background(&tids[i], nullptr, CallerLoop, &callers[i]);
    }
    for (fiber_t tid : tids) fiber_join(tid, nullptr);
}

// One Get; 0 and *same, or the call's error code.
int GetAndCompare(Shared* s, uint64_t session, uint32_t layer,
                  const Stamp* stamp, const std::string* body, bool* same) {
    Controller cntl;
    cntl.set_timeout_ms(s->timeout_ms);
    cntl.set_max_retry(0);
    kvpb::GetRequest req;
    kvpb::GetResponse res;
    req.set_session(session);
    req.set_layer(layer);
    s->stub->Get(&cntl, &req, &res, nullptr);
    if (cntl.Failed()) return cntl.ErrorCode() != 0 ? cntl.ErrorCode() : -1;
    if (same != nullptr) {
        *same = SameBytes(cntl.response_attachment(), *stamp, *body);
    }
    return 0;
}

double CpuSeconds() {
    rusage ru;
    getrusage(RUSAGE_SELF, &ru);
    return ru.ru_utime.tv_sec + ru.ru_stime.tv_sec +
           (ru.ru_utime.tv_usec + ru.ru_stime.tv_usec) / 1e6;
}

bool EndsWith(const std::string& s, const char* suffix) {
    const size_t n = strlen(suffix);
    return s.size() >= n && s.compare(s.size() - n, n, suffix) == 0;
}

// This process's stage table and safety-net counters, all cumulative, as
// {"status":{"stages":{...}},"vars":{"<name>":N,...}}: what a scrape of the
// server's portal gives the harness for the server (served.py `scrape`).
std::string ProcessDump() {
    std::string vars;
    for (const std::string& name : Variable::list_exposed()) {
        std::string value;
        if ((EndsWith(name, "_timeouts") ||
             EndsWith(name, "_timeouts_found_work")) &&
            Variable::describe_exposed(name, &value) &&
            IsNumericLiteral(value)) {
            vars += (vars.empty() ? "\"" : ",\"") + name + "\":" + value;
        }
    }
    return "{\"status\":{\"stages\":" + stage::DumpJson() +
           "},\"vars\":{" + vars + "}}";
}

template <typename T>
std::string Joined(const std::vector<T>& values) {
    std::string out;
    for (const T& v : values) {
        out += (out.empty() ? "" : ",") + std::to_string(v);
    }
    return out;
}

}  // namespace

int main(int argc, char** argv) {
    int port = 0, ncallers = 0;
    size_t nbytes = 0;
    uint64_t seed = 0;
    uint32_t layers = 0;
    double seconds = 0;
    int64_t warm_ms = 500, timeout_ms = 10000;

    const char* sample_out = nullptr;
    for (int i = 1; i + 1 < argc; i += 2) {
        const std::string k = argv[i];
        const char* v = argv[i + 1];
        if (k == "--port") port = atoi(v);
        else if (k == "--callers") ncallers = atoi(v);
        else if (k == "--bytes") nbytes = strtoull(v, nullptr, 10);
        else if (k == "--layers") layers = (uint32_t)strtoul(v, nullptr, 10);
        else if (k == "--seed") seed = strtoull(v, nullptr, 10);
        else if (k == "--seconds") seconds = atof(v);
        else if (k == "--warm-ms") warm_ms = atoll(v);
        else if (k == "--timeout-ms") timeout_ms = atoll(v);
        else if (k == "--sample-out") sample_out = v;
        else {
            fprintf(stderr, "kv_load: unknown option %s\n", k.c_str());
            return 2;
        }
    }
    if (port <= 0 || ncallers <= 0 || ncallers > 255 || nbytes < 24 ||
        nbytes % 8 != 0 || layers == 0 || seconds <= 0 ||
        sample_out == nullptr) {
        fprintf(stderr, "kv_load: --port --callers (<= 255) --bytes (a "
                        "multiple of 8, >= 24) --layers --seed --seconds "
                        "--sample-out are required\n");
        return 2;
    }
    prctl(PR_SET_PDEATHSIG, SIGKILL);  // never outlive the harness

    // As tools/echo_bench.cc sets them on both sides of its --xproc round.
    FLAGS_socket_send_buffer_size.set(1 << 20);
    FLAGS_socket_recv_buffer_size.set(1 << 20);
    // Every caller's base buffer is posted to the server by reference, so it
    // has to lie in the region the server maps, for the whole run: the
    // program's 64 MiB, or twice the N x B of them where that is more.
    if (IciBlockPool::Init(std::max<size_t>(
            64u << 20, 2 * (size_t)ncallers * nbytes)) != 0) {
        return 1;
    }
    Channel channel;
    ChannelOptions copts;
    copts.timeout_ms = timeout_ms;
    copts.max_retry = 0;
    EndPoint ep;
    str2endpoint("127.0.0.1", port, &ep);
    if (channel.InitIci(ep, &copts) != 0) {
        fprintf(stderr, "kv_load: InitIci to port %d failed\n", port);
        return 1;
    }
    kvpb::Cache_Stub stub(&channel);

    Shared shared{&stub, timeout_ms, seed, (uint64_t)ncallers, layers, 0, 0,
                  false};
    std::vector<Caller> callers((size_t)ncallers);
    uint32_t body_crc = (uint32_t)crc32(0L, Z_NULL, 0);
    for (int i = 0; i < ncallers; ++i) {
        Caller& c = callers[(size_t)i];
        c.shared = &shared;
        c.idx = (uint64_t)i;
        c.body = MakeBody(seed, (uint64_t)i, nbytes - kStamp);
        c.body_buf.append(c.body);
        // The body's share of sum x[j] * (2j + 1): it starts at word 4.
        for (size_t off = 0, j = kStamp / 4; off < c.body.size();
             off += 4, ++j) {
            uint32_t w;
            memcpy(&w, &c.body[off], 4);
            c.body_word += w * (uint32_t)(2 * j + 1);
        }
        body_crc = (uint32_t)crc32(body_crc, (const Bytef*)c.body.data(),
                                   (uInt)c.body.size());
    }

    shared.t_end_ns = NowNs() + warm_ms * 1000000LL;
    RunCallers(callers);
    for (Caller& c : callers) {
        c.lat_ns.reserve((size_t)(seconds * 200000.0 / ncallers) + 1024);
        c.per_s.reserve((size_t)seconds + 64);
    }

    printf("READY\n");
    fflush(stdout);
    char line[64];
    if (read(0, line, sizeof(line)) <= 0) return 1;  // parent went away

    const std::string dump_before = ProcessDump();
    shared.record = true;
    const double cpu0 = CpuSeconds();
    const int64_t t0 = NowNs();
    shared.t_start_ns = t0;
    shared.t_end_ns = t0 + (int64_t)(seconds * 1e9);
    RunCallers(callers);
    const double cpu1 = CpuSeconds();
    const std::string dump_after = ProcessDump();

    int64_t attempted = 0, ok = 0, rpc_failed = 0, mismatched = 0;
    int64_t t_last = t0;
    std::map<int, int64_t> errors;
    FILE* f = fopen(sample_out, "wb");
    if (f == nullptr) {
        fprintf(stderr, "kv_load: cannot write %s\n", sample_out);
        return 1;
    }
    std::string sessions, last_puts;
    std::vector<int64_t> per_s;
    int64_t admitted_moved = 0;
    for (Caller& c : callers) {
        if (c.per_s.size() > per_s.size()) per_s.resize(c.per_s.size(), 0);
        for (size_t i = 0; i < c.per_s.size(); ++i) per_s[i] += c.per_s[i];
        attempted += c.attempted;
        ok += c.ok;
        rpc_failed += c.rpc_failed;
        mismatched += c.mismatched;
        t_last = std::max(t_last, c.last_done_ns);
        for (auto& kv : c.errors) errors[kv.first] += kv.second;
        if (!c.lat_ns.empty() &&
            fwrite(c.lat_ns.data(), 8, c.lat_ns.size(), f) !=
                c.lat_ns.size()) {
            fprintf(stderr, "kv_load: short write to %s\n", sample_out);
            return 1;
        }
        for (const Session& s : c.sessions) {
            if (s.acked == 0) continue;
            admitted_moved += s.admitted_moved;
            sessions += (sessions.empty() ? "[" : ",[") +
                        std::to_string(s.id) + "," +
                        std::to_string(s.admitted) + "," +
                        std::to_string(s.acked) + "]";
        }
        last_puts += (last_puts.empty() ? "[" : ",[") +
                     std::to_string(c.last_session) + "," +
                     std::to_string(c.last_layer) + "," +
                     std::to_string(c.last_seq) + "," +
                     std::to_string(c.last_word) + "]";
    }
    fclose(f);
    std::string errs;
    for (auto& kv : errors) {
        errs += (errs.empty() ? "\"" : ",\"") + std::to_string(kv.first) +
                "\":" + std::to_string(kv.second);
    }
    // window_s runs from the first start to the LAST completion: operations
    // in flight when the window closes are drained inside it.
    printf("{\"attempted\":%lld,\"ok\":%lld,\"rpc_failed\":%lld,"
           "\"mismatched\":%lld,\"window_s\":%.9f,\"client_cpu_s\":%.6f,"
           "\"bytes_each\":%zu,\"body_crc32\":%u,\"sessions\":[%s],"
           "\"admitted_moved\":%lld,\"last_put\":[%s],\"errors\":{%s},"
           "\"workers\":%d,\"per_s\":[%s],\"client_before\":%s,"
           "\"client_after\":%s}\n",
           (long long)attempted, (long long)ok, (long long)rpc_failed,
           (long long)mismatched, (double)(t_last - t0) / 1e9, cpu1 - cpu0,
           nbytes, body_crc, sessions.c_str(), (long long)admitted_moved,
           last_puts.c_str(), errs.c_str(), fiber_get_worker_count(),
           Joined(per_s).c_str(), dump_before.c_str(), dump_after.c_str());
    fflush(stdout);

    // The readback, outside every timed number.
    memset(line, 0, sizeof(line));
    if (read(0, line, sizeof(line) - 1) <= 0) return 1;
    unsigned long long evicted = 0;
    if (sscanf(line, "READBACK %llu", &evicted) != 1) {
        fprintf(stderr, "kv_load: expected READBACK <session>, got %s\n",
                line);
        return 1;
    }
    int64_t checked = 0, wrong = 0, get_failed = 0;
    for (Caller& c : callers) {
        int done = 0;
        for (size_t i = c.sessions.size(); i-- > 0 &&
                                           done < kReadbackSessions;) {
            const Session& s = c.sessions[i];
            if (s.acked != layers) continue;  // not completed
            ++done;
            for (int g = 0; g < std::min<int>(kReadbackLayers, layers); ++g) {
                // Seeded, and distinct: a stride through the layers from a
                // seeded start.
                const uint32_t layer = (uint32_t)(
                    (PayloadWord(seed, 2 * ncallers + c.idx, s.id) +
                     (uint64_t)g * (layers / std::min<uint32_t>(
                                        kReadbackLayers, layers))) % layers);
                const Stamp stamp =
                    MakeStamp(s.id, layer, c.idx, s.first_seq + layer);
                bool same = false;
                ++checked;
                if (GetAndCompare(&shared, s.id, layer, &stamp, &c.body,
                                  &same) != 0) {
                    ++get_failed;
                } else if (!same) {
                    ++wrong;
                }
            }
        }
    }
    const int evicted_code =
        evicted == 0 ? 0
                     : GetAndCompare(&shared, evicted, 0, nullptr, nullptr,
                                     nullptr);
    printf("{\"readback_checked\":%lld,\"readback_wrong\":%lld,"
           "\"readback_failed\":%lld,\"evicted_session\":%llu,"
           "\"evicted_code\":%d}\n",
           (long long)checked, (long long)wrong, (long long)get_failed,
           evicted, evicted_code);
    fflush(stdout);
    _exit(0);  // as the program's tools: no static teardown under live threads
}
