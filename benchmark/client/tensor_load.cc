// The benchmark's own load generator for the cell of `tensor_echo_1chip`: N
// caller fibers, each a SYNC tensor.Step (tensorpb.Tensor/Step) of a seeded
// attachment back to back (a closed loop: in-flight work is bounded by N, so
// a stall makes replies late and can make none fail), for a fixed wall-clock
// window.
//
//   tensor_load --port P --callers N --bytes B --seed S --key K --seconds T
//               --sample-out FILE [--warm-ms W] [--timeout-ms D]
//
// Protocol with the parent (benchmark/drivers/tensor.py), as echo_load.cc:
//   1. connect (Channel::InitIci: TCP handshake, then the shm link), warm
//      every caller for W ms, print "READY\n";
//   2. wait for a line on stdin (the parent brackets the window with its
//      counter scrapes and the profiler), then run the window;
//   3. every operation STARTED inside the window is waited for (the
//      per-call deadline bounds that drain) and its reply compared byte for
//      byte with the answer computed HERE from what was sent; latencies of
//      all of them go to FILE as raw little-endian uint64 nanoseconds; one
//      JSON line goes to stdout. As echo_load.cc's, the line also carries
//      THIS process's half of a served call: the library's cumulative stage
//      table and its `*_timeouts` / `*_timeouts_found_work` counters, dumped
//      after the warm-up before the first timed operation (`client_before`)
//      and after the drain (`client_after`), each in the shape of one scrape
//      of the server's portal. Both dumps lie outside `window_s`, the
//      latency sample and `client_cpu_s`; benchmark/stages.py takes
//      after - before.
//
// Request of caller c, operation n (n counts from 1, warm-up included), B
// bytes, B a multiple of 8 and at least 16:
//   bytes [0,8)  little-endian (c << 48) | n
//   bytes [8,B)  little-endian words mix64(seed, c, j), see PayloadWord
//                (benchmark/payload.py makes the same bytes in numpy).
// Reply, B + 4 bytes, over the request as little-endian uint32 words x:
//   y[0], y[1] = x[0], x[1]; y[j] = x[j] ^ K for j >= 2; then the word
//   w = sum over j of x[j] * (2j + 1), wrapping (benchmark/
//   tensor_reference.py writes the same in numpy, and the parent holds this
//   file's digests to it).
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

#include "tbase/endpoint.h"
#include "tbase/flags.h"
#include "tbase/iobuf.h"
#include "tfiber/fiber.h"
#include "tici/block_pool.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "tensor.pb.h"
#include "tvar/stage_recorder.h"
#include "tvar/variable.h"

using namespace tpurpc;

DECLARE_int32(socket_send_buffer_size);
DECLARE_int32(socket_recv_buffer_size);

namespace {

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

// got == want_head(8) + want_body + want_word(4), compared in place block by
// block.
bool SameBytes(const IOBuf& got, const char* head, const std::string& mid,
               const char* tail) {
    const size_t total = 8 + mid.size() + 4;
    if (got.size() != total) return false;
    size_t pos = 0;
    for (size_t i = 0; i < got.backing_block_num(); ++i) {
        size_t len = 0;
        const char* p = got.backing_block_data(i, &len);
        while (len > 0) {
            const char* want;
            size_t room;
            if (pos < 8) {
                want = head + pos, room = 8 - pos;
            } else if (pos < 8 + mid.size()) {
                want = mid.data() + (pos - 8), room = 8 + mid.size() - pos;
            } else {
                want = tail + (pos - 8 - mid.size()), room = total - pos;
            }
            const size_t n = std::min(len, room);
            if (memcmp(p, want, n) != 0) return false;
            p += n, len -= n, pos += n;
        }
    }
    return pos == total;
}

uint32_t Crc32Of(const IOBuf& buf) {
    uint32_t crc = (uint32_t)crc32(0L, Z_NULL, 0);
    for (size_t i = 0; i < buf.backing_block_num(); ++i) {
        size_t len = 0;
        const char* p = buf.backing_block_data(i, &len);
        crc = (uint32_t)crc32(crc, (const Bytef*)p, (uInt)len);
    }
    return crc;
}

struct Shared {
    tensorpb::Tensor_Stub* stub;
    int64_t timeout_ms;
    int64_t t_start_ns;  // the window's start (per-second counts)
    int64_t t_end_ns;    // no operation starts at or after this
    bool record;       // false during the warm-up
};

struct Caller {
    Shared* shared = nullptr;
    uint64_t idx = 0;
    std::string body;  // bytes [8,B) of every request
    IOBuf body_buf;    // the same, appended by reference to each request
    std::string want;  // bytes [8,B) of every reply: body ^ key, word by word
    uint32_t body_word = 0;  // the body's part of the integrity word
    uint64_t seq = 0;
    int64_t attempted = 0, ok = 0, rpc_failed = 0, mismatched = 0;
    int64_t last_done_ns = 0;
    uint32_t last_reply_crc = 0;
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
        const uint64_t tag = (c->idx << 48) | ++c->seq;
        Controller cntl;
        cntl.set_timeout_ms(s->timeout_ms);
        cntl.set_max_retry(0);
        tensorpb::StepRequest req;
        tensorpb::StepResponse res;
        req.set_send_ts_us(t0 / 1000);
        cntl.request_attachment().append(&tag, 8);
        cntl.request_attachment().append(c->body_buf);
        s->stub->Step(&cntl, &req, &res, nullptr);
        const int64_t t1 = NowNs();
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
                fprintf(stderr, "tensor_load: caller %llu rpc failed (%d): %s\n",
                        (unsigned long long)c->idx, cntl.ErrorCode(),
                        cntl.ErrorText().c_str());
            }
        } else if (const uint32_t word =
                       c->body_word + (uint32_t)tag + (uint32_t)(tag >> 32) * 3u;
                   !SameBytes(cntl.response_attachment(), (const char*)&tag,
                              c->want, (const char*)&word)) {
            ++c->mismatched;
        } else {
            ++c->ok;
        }
        c->last_reply_crc = Crc32Of(cntl.response_attachment());
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

}  // namespace

int main(int argc, char** argv) {
    int port = 0, ncallers = 0;
    size_t nbytes = 0;
    uint64_t seed = 0;
    double seconds = 0;
    int64_t warm_ms = 500, timeout_ms = 10000;
    uint32_t key = 0;

    const char* sample_out = nullptr;
    for (int i = 1; i + 1 < argc; i += 2) {
        const std::string k = argv[i];
        const char* v = argv[i + 1];
        if (k == "--port") port = atoi(v);
        else if (k == "--callers") ncallers = atoi(v);
        else if (k == "--bytes") nbytes = strtoull(v, nullptr, 10);
        else if (k == "--seed") seed = strtoull(v, nullptr, 10);
        else if (k == "--seconds") seconds = atof(v);
        else if (k == "--warm-ms") warm_ms = atoll(v);
        else if (k == "--timeout-ms") timeout_ms = atoll(v);
        else if (k == "--sample-out") sample_out = v;
        else if (k == "--key") key = (uint32_t)strtoull(v, nullptr, 10);
        else {
            fprintf(stderr, "tensor_load: unknown option %s\n", k.c_str());
            return 2;
        }
    }
    if (port <= 0 || ncallers <= 0 || ncallers > 4096 || nbytes < 16 ||
        nbytes % 8 != 0 || seconds <= 0 || sample_out == nullptr) {
        fprintf(stderr, "tensor_load: --port --callers --bytes (a multiple "
                        "of 8, >= 16) --seed --key --seconds --sample-out "
                        "are required\n");
        return 2;
    }
    prctl(PR_SET_PDEATHSIG, SIGKILL);  // never outlive the harness

    // As tools/echo_bench.cc sets them on both sides of its --xproc round.
    FLAGS_socket_send_buffer_size.set(1 << 20);
    FLAGS_socket_recv_buffer_size.set(1 << 20);
    if (IciBlockPool::Init() != 0) return 1;
    Channel channel;
    ChannelOptions copts;
    copts.timeout_ms = timeout_ms;
    copts.max_retry = 0;
    EndPoint ep;
    str2endpoint("127.0.0.1", port, &ep);
    if (channel.InitIci(ep, &copts) != 0) {
        fprintf(stderr, "tensor_load: InitIci to port %d failed\n", port);
        return 1;
    }
    tensorpb::Tensor_Stub stub(&channel);

    Shared shared{&stub, timeout_ms, 0, 0, false};
    std::vector<Caller> callers((size_t)ncallers);
    uint32_t body_crc = (uint32_t)crc32(0L, Z_NULL, 0);
    for (int i = 0; i < ncallers; ++i) {
        Caller& c = callers[(size_t)i];
        c.shared = &shared;
        c.idx = (uint64_t)i;
        c.body = MakeBody(seed, (uint64_t)i, nbytes - 8);
        c.body_buf.append(c.body);
        // What the service must make of the body: every word ^ key, and
        // the body's share of sum x[j] * (2j + 1) (the body starts at
        // word 2).
        c.want = c.body;
        for (size_t off = 0, j = 2; off < c.want.size(); off += 4, ++j) {
            uint32_t w;
            memcpy(&w, &c.body[off], 4);
            c.body_word += w * (uint32_t)(2 * j + 1);
            w ^= key;
            memcpy(&c.want[off], &w, 4);
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
    char go[8];
    if (read(0, go, sizeof(go)) <= 0) return 1;  // parent went away

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
        fprintf(stderr, "tensor_load: cannot write %s\n", sample_out);
        return 1;
    }
    std::string seqs, crcs;
    std::vector<int64_t> per_s;
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
            fprintf(stderr, "tensor_load: short write to %s\n", sample_out);
            return 1;
        }
        seqs += (seqs.empty() ? "" : ",") + std::to_string(c.seq);
        crcs += (crcs.empty() ? "" : ",") + std::to_string(c.last_reply_crc);
    }
    fclose(f);
    std::string secs;
    for (int64_t n : per_s) {
        secs += (secs.empty() ? "" : ",") + std::to_string(n);
    }
    std::string errs;
    for (auto& kv : errors) {
        errs += (errs.empty() ? "\"" : ",\"") + std::to_string(kv.first) +
                "\":" + std::to_string(kv.second);
    }
    // window_s runs from the first start to the LAST completion: operations
    // in flight when the window closes are drained inside it.
    printf("{\"attempted\":%lld,\"ok\":%lld,\"rpc_failed\":%lld,"
           "\"mismatched\":%lld,\"window_s\":%.9f,\"client_cpu_s\":%.6f,"
           "\"bytes_each\":%zu,\"body_crc32\":%u,\"last_seq\":[%s],"
           "\"last_reply_crc32\":[%s],\"errors\":{%s},\"workers\":%d,"
           "\"per_s\":[%s],\"client_before\":%s,\"client_after\":%s}\n",
           (long long)attempted, (long long)ok, (long long)rpc_failed,
           (long long)mismatched, (double)(t_last - t0) / 1e9, cpu1 - cpu0,
           nbytes, body_crc, seqs.c_str(), crcs.c_str(), errs.c_str(),
           fiber_get_worker_count(), secs.c_str(), dump_before.c_str(),
           dump_after.c_str());
    fflush(stdout);
    _exit(0);  // as the program's tools: no static teardown under live threads
}
