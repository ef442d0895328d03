#include "tbase/flight_recorder.h"

#include <errno.h>
#include <fcntl.h>
#include <signal.h>
#include <string.h>
#include <sys/prctl.h>
#include <sys/syscall.h>
#include <unistd.h>

#include <cstdio>

#include "tbase/flags.h"
#include "tbase/time.h"
#include "tvar/reducer.h"

// Always-on by default: the whole point of a flight recorder is that it is
// already running when the crash happens. -flight_recorder_enabled=0 exists
// for pricing the recorder itself (ROADMAP A9) and A/B debugging.
DEFINE_bool(flight_recorder_enabled, true,
            "Record flight events into per-thread rings");
DEFINE_int64(flight_recorder_ring, 4096,
             "Events per thread ring (rounded up to a power of two; applies "
             "to rings registered after the change)");
DEFINE_string(flight_blackbox_path, "",
              "If set, fatal signals (and unclean tool exits) dump all "
              "flight rings to this file");

namespace tpurpc {
namespace flight {

const char* const kKindNames[] = {
    "NONE",
    "RPC_ISSUE",
    "RPC_DISPATCH",
    "RPC_HANDLER_IN",
    "RPC_HANDLER_OUT",
    "RPC_WRITE",
    "RPC_RESP_RECV",
    "VERB_POST",
    "VERB_WIRE",
    "VERB_COMPLETE",
    "VERB_REAP",
    "LEASE_PIN",
    "LEASE_ARM",
    "LEASE_RELEASE",
    "LEASE_EXPIRE",
    "LEASE_PEER_DEATH",
    "STREAM_CHUNK",
    "STREAM_CREDIT_STALL",
    "STREAM_RESUME",
    "COLL_STEP",
    "COLL_REFORM",
    "SCHED_INLINE",
    "SCHED_PARK",
    "CHAOS_INJECT",
    "OUTLIER_EJECT",
    "OUTLIER_REINSTATE",
};
static_assert(sizeof(kKindNames) / sizeof(kKindNames[0]) == kKindCount,
              "kKindNames must cover every EventKind");

namespace internal {

std::atomic<bool> g_on{true};
std::atomic<int> g_nrings{0};
ThreadRing* g_rings[kMaxRings] = {};

}  // namespace internal

namespace {

using internal::Event;
using internal::g_nrings;
using internal::g_on;
using internal::g_rings;
using internal::kMaxRings;
using internal::ThreadRing;

// Events recorded on threads that could not get a ring slot (registry full).
std::atomic<uint64_t> g_lost{0};
std::atomic<uint64_t> g_dump_count{0};

// Crash-handler state. The path lives in a fixed buffer (no std::string in
// a signal handler) and is refreshed by the flag's on_change hook.
char g_crash_path[256] = {0};
std::atomic<bool> g_handler_installed{false};
std::atomic<bool> g_dumping{false};

char g_node_name[64] = {0};

// Clock anchors captured when the first ring registers: a (wall, mono, tsc)
// triple lets the merge tool convert any ring's tsc to this node's wall
// clock, and the envelope technique then aligns nodes to each other.
struct Anchors {
    int64_t wall_us;
    int64_t mono_us;
    uint64_t tsc;
    double tpu;
};
Anchors g_anchors = {0, 0, 0, 0.0};
std::atomic<bool> g_anchored{false};

void CaptureAnchorsOnce() {
    bool expected = false;
    if (!g_anchored.compare_exchange_strong(expected, true)) return;
    g_anchors.wall_us = gettimeofday_us();
    g_anchors.mono_us = monotonic_time_us();
    g_anchors.tsc = cpuwide_ticks();
    g_anchors.tpu = ticks_per_us();
}

uint32_t RoundPow2(int64_t v) {
    if (v < 64) v = 64;
    if (v > (1 << 20)) v = 1 << 20;
    uint32_t cap = 64;
    while ((int64_t)cap < v) cap <<= 1;
    return cap;
}

thread_local ThreadRing* t_ring = nullptr;
thread_local bool t_lost = false;

ThreadRing* RegisterRing() {
    int idx = g_nrings.fetch_add(1, std::memory_order_relaxed);
    if (idx >= kMaxRings) {
        // Registry full: keep the counter honest for later arrivals but do
        // not let it run away.
        g_nrings.store(kMaxRings, std::memory_order_relaxed);
        t_lost = true;
        return nullptr;
    }
    CaptureAnchorsOnce();
    uint32_t cap = RoundPow2(FLAGS_flight_recorder_ring.get());
    ThreadRing* r = new ThreadRing();
    r->slots = new Event[cap]();
    r->cap = cap;
    r->tid = (uint32_t)syscall(SYS_gettid);
    memset(r->name, 0, sizeof(r->name));
    prctl(PR_GET_NAME, (unsigned long)r->name, 0, 0, 0);
    r->name[sizeof(r->name) - 1] = '\0';
    r->next.store(0, std::memory_order_relaxed);
    // Publish after the ring is fully initialized: dumpers scan g_rings.
    __atomic_store_n(&g_rings[idx], r, __ATOMIC_RELEASE);
    return r;
}

// Binary dump format (consumed by tools/blackbox_merge.py — versioned).
struct FileHeader {
    char magic[8];  // "TFRBOX1\0"
    uint32_t version;
    uint32_t pid;
    int64_t wall_us;     // anchors captured at recorder init
    int64_t mono_us;
    uint64_t tsc;
    double ticks_per_us;
    int64_t dump_mono_us;  // re-captured at dump time (tsc drift check)
    uint64_t dump_tsc;
    uint32_t nrings;
    uint32_t reserved;
    char node[64];
};

struct RingHeader {
    char magic[8];  // "TFRRING\0"
    uint32_t tid;
    uint32_t cap;
    uint64_t next;
    uint32_t nvalid;
    uint32_t reserved;
    char name[16];
};

// write(2) loop, EINTR-safe, usable from a signal handler.
bool WriteAll(int fd, const void* buf, size_t n) {
    const char* p = (const char*)buf;
    while (n > 0) {
        ssize_t w = write(fd, p, n);
        if (w < 0) {
            if (errno == EINTR) continue;
            return false;
        }
        p += w;
        n -= (size_t)w;
    }
    return true;
}

void CrashHandler(int sig, siginfo_t*, void*) {
    // One dump per process: a second fault while dumping must not recurse.
    bool expected = false;
    if (g_dumping.compare_exchange_strong(expected, true) &&
        g_crash_path[0] != '\0') {
        int fd = open(g_crash_path, O_WRONLY | O_CREAT | O_TRUNC, 0644);
        if (fd >= 0) {
            if (DumpToFd(fd) > 0) {
                g_dump_count.fetch_add(1, std::memory_order_relaxed);
            }
            close(fd);
        }
    }
    // Restore default disposition and re-raise so the exit status still
    // reports the original signal (tests assert -SIGSEGV).
    signal(sig, SIG_DFL);
    raise(sig);
}

int64_t PassiveEvents(void*) { return (int64_t)TotalEvents(); }
int64_t PassiveDropped(void*) { return (int64_t)TotalDropped(); }
int64_t PassiveHighwater(void*) { return (int64_t)RingHighwater(); }
int64_t PassiveDumps(void*) { return (int64_t)DumpCount(); }

// Append one JSON-escaped string (ring/thread names are prctl-limited ASCII,
// but stay defensive).
void AppendJsonString(std::string* out, const char* s) {
    out->push_back('"');
    for (; *s; ++s) {
        unsigned char c = (unsigned char)*s;
        if (c == '"' || c == '\\') {
            out->push_back('\\');
            out->push_back((char)c);
        } else if (c < 0x20) {
            char buf[8];
            snprintf(buf, sizeof(buf), "\\u%04x", c);
            *out += buf;
        } else {
            out->push_back((char)c);
        }
    }
    out->push_back('"');
}

}  // namespace

void internal::RecordSlow(EventKind kind, uint64_t a, uint64_t b) {
    ThreadRing* r = t_ring;
    if (r == nullptr) {
        if (t_lost) {
            g_lost.fetch_add(1, std::memory_order_relaxed);
            return;
        }
        r = RegisterRing();
        if (r == nullptr) {
            g_lost.fetch_add(1, std::memory_order_relaxed);
            return;
        }
        t_ring = r;
    }
    uint64_t next = r->next.load(std::memory_order_relaxed);
    Event& e = r->slots[next & (r->cap - 1)];
    e.tsc = cpuwide_ticks();
    e.kind = kind;
    e.seq = (uint32_t)next;
    e.a = a;
    e.b = b;
    // Release: a dumper that reads `next` sees fully-written slots below it.
    r->next.store(next + 1, std::memory_order_release);
}

void SetNodeName(const std::string& name) {
    strncpy(g_node_name, name.c_str(), sizeof(g_node_name) - 1);
    g_node_name[sizeof(g_node_name) - 1] = '\0';
}

int64_t DumpToFd(int fd) {
    CaptureAnchorsOnce();
    FileHeader h;
    memset(&h, 0, sizeof(h));
    memcpy(h.magic, "TFRBOX1\0", 8);
    h.version = 1;
    h.pid = (uint32_t)getpid();
    h.wall_us = g_anchors.wall_us;
    h.mono_us = g_anchors.mono_us;
    h.tsc = g_anchors.tsc;
    h.ticks_per_us = g_anchors.tpu;
    h.dump_mono_us = monotonic_time_us();
    h.dump_tsc = cpuwide_ticks();
    int n = g_nrings.load(std::memory_order_acquire);
    if (n > kMaxRings) n = kMaxRings;
    int live = 0;
    for (int i = 0; i < n; ++i) {
        if (__atomic_load_n(&g_rings[i], __ATOMIC_ACQUIRE) != nullptr) ++live;
    }
    h.nrings = (uint32_t)live;
    memcpy(h.node, g_node_name, sizeof(h.node));
    int64_t total = 0;
    if (!WriteAll(fd, &h, sizeof(h))) return -1;
    total += (int64_t)sizeof(h);
    for (int i = 0; i < n; ++i) {
        ThreadRing* r = __atomic_load_n(&g_rings[i], __ATOMIC_ACQUIRE);
        if (r == nullptr) continue;
        RingHeader rh;
        memset(&rh, 0, sizeof(rh));
        memcpy(rh.magic, "TFRRING\0", 8);
        rh.tid = r->tid;
        rh.cap = r->cap;
        rh.next = r->next.load(std::memory_order_acquire);
        uint64_t nvalid = rh.next < r->cap ? rh.next : r->cap;
        rh.nvalid = (uint32_t)nvalid;
        memcpy(rh.name, r->name, sizeof(rh.name));
        if (!WriteAll(fd, &rh, sizeof(rh))) return -1;
        // Raw slot order: the merger orders by each event's seq field and
        // drops anything outside [next-cap, next) (torn or stale slots).
        if (nvalid > 0 &&
            !WriteAll(fd, r->slots, nvalid * sizeof(Event))) {
            return -1;
        }
        total += (int64_t)(sizeof(rh) + nvalid * sizeof(Event));
    }
    return total;
}

bool DumpToFile(const std::string& path) {
    int fd = open(path.c_str(), O_WRONLY | O_CREAT | O_TRUNC, 0644);
    if (fd < 0) return false;
    int64_t n = DumpToFd(fd);
    close(fd);
    if (n <= 0) return false;
    g_dump_count.fetch_add(1, std::memory_order_relaxed);
    return true;
}

void DumpJson(std::string* out) {
    CaptureAnchorsOnce();
    char buf[256];
    *out += "{\"node\":";
    AppendJsonString(out, g_node_name);
    snprintf(buf, sizeof(buf),
             ",\"pid\":%u,\"wall_us\":%lld,\"mono_us\":%lld,\"tsc\":%llu,"
             "\"ticks_per_us\":%.6f,\"dump_mono_us\":%lld,\"dump_tsc\":%llu,"
             "\"dropped\":%llu,\"rings\":[",
             (unsigned)getpid(), (long long)g_anchors.wall_us,
             (long long)g_anchors.mono_us, (unsigned long long)g_anchors.tsc,
             g_anchors.tpu, (long long)monotonic_time_us(),
             (unsigned long long)cpuwide_ticks(),
             (unsigned long long)TotalDropped());
    *out += buf;
    int n = g_nrings.load(std::memory_order_acquire);
    if (n > kMaxRings) n = kMaxRings;
    bool first_ring = true;
    for (int i = 0; i < n; ++i) {
        ThreadRing* r = __atomic_load_n(&g_rings[i], __ATOMIC_ACQUIRE);
        if (r == nullptr) continue;
        if (!first_ring) out->push_back(',');
        first_ring = false;
        uint64_t next = r->next.load(std::memory_order_acquire);
        uint64_t nvalid = next < r->cap ? next : r->cap;
        snprintf(buf, sizeof(buf), "{\"tid\":%u,\"cap\":%u,\"next\":%llu,",
                 r->tid, r->cap, (unsigned long long)next);
        *out += buf;
        *out += "\"name\":";
        AppendJsonString(out, r->name);
        *out += ",\"events\":[";
        // Oldest-first: walk [next-nvalid, next). The owner may keep
        // recording while we read — drop events whose seq no longer matches
        // their slot (overwritten under us).
        bool first_ev = true;
        for (uint64_t s = next - nvalid; s < next; ++s) {
            const Event& e = r->slots[s & (r->cap - 1)];
            if (e.seq != (uint32_t)s) continue;
            uint32_t kind = e.kind < kKindCount ? e.kind : 0;
            if (!first_ev) out->push_back(',');
            first_ev = false;
            snprintf(buf, sizeof(buf),
                     "{\"tsc\":%llu,\"seq\":%llu,\"k\":%u,\"kind\":\"%s\","
                     "\"a\":%llu,\"b\":%llu}",
                     (unsigned long long)e.tsc, (unsigned long long)s, e.kind,
                     kKindNames[kind], (unsigned long long)e.a,
                     (unsigned long long)e.b);
            *out += buf;
        }
        *out += "]}";
    }
    *out += "]}";
}

void DumpText(std::string* out) {
    CaptureAnchorsOnce();
    char buf[256];
    snprintf(buf, sizeof(buf),
             "flight recorder: node=%s pid=%u enabled=%d events=%llu "
             "dropped=%llu dumps=%llu\n",
             g_node_name[0] ? g_node_name : "?", (unsigned)getpid(),
             (int)g_on.load(std::memory_order_relaxed),
             (unsigned long long)TotalEvents(),
             (unsigned long long)TotalDropped(),
             (unsigned long long)DumpCount());
    *out += buf;
    int n = g_nrings.load(std::memory_order_acquire);
    if (n > kMaxRings) n = kMaxRings;
    const double tpu = g_anchors.tpu > 0 ? g_anchors.tpu : 1.0;
    for (int i = 0; i < n; ++i) {
        ThreadRing* r = __atomic_load_n(&g_rings[i], __ATOMIC_ACQUIRE);
        if (r == nullptr) continue;
        uint64_t next = r->next.load(std::memory_order_acquire);
        uint64_t nvalid = next < r->cap ? next : r->cap;
        snprintf(buf, sizeof(buf), "\n[ring %d] tid=%u name=%s events=%llu\n",
                 i, r->tid, r->name, (unsigned long long)next);
        *out += buf;
        // Show the newest 32 events per ring: the portal page is a glance
        // surface; full history goes through ?format=json or the dump file.
        uint64_t shown = nvalid < 32 ? nvalid : 32;
        for (uint64_t s = next - shown; s < next; ++s) {
            const Event& e = r->slots[s & (r->cap - 1)];
            if (e.seq != (uint32_t)s) continue;
            uint32_t kind = e.kind < kKindCount ? e.kind : 0;
            double rel_us =
                g_anchors.tsc <= e.tsc
                    ? (double)(e.tsc - g_anchors.tsc) / tpu
                    : -(double)(g_anchors.tsc - e.tsc) / tpu;
            snprintf(buf, sizeof(buf),
                     "  +%-12.1f %-20s a=%-20llu b=%llu\n", rel_us,
                     kKindNames[kind], (unsigned long long)e.a,
                     (unsigned long long)e.b);
            *out += buf;
        }
    }
}

void InstallCrashHandler(const std::string& path) {
    if (!path.empty()) {
        // Route through the flag so /flags shows the active path and the
        // on_change hook keeps g_crash_path in sync.
        FLAGS_flight_blackbox_path.set(path);
    }
    bool expected = false;
    if (!g_handler_installed.compare_exchange_strong(expected, true)) return;
    struct sigaction sa;
    memset(&sa, 0, sizeof(sa));
    sa.sa_sigaction = CrashHandler;
    sa.sa_flags = SA_SIGINFO;
    sigemptyset(&sa.sa_mask);
    const int sigs[] = {SIGSEGV, SIGABRT, SIGBUS, SIGFPE, SIGILL};
    for (int sig : sigs) {
        sigaction(sig, &sa, nullptr);
    }
}

bool DumpToConfiguredPath() {
    if (g_crash_path[0] == '\0') return false;
    return DumpToFile(g_crash_path);
}

uint64_t TotalEvents() {
    uint64_t total = 0;
    int n = g_nrings.load(std::memory_order_acquire);
    if (n > kMaxRings) n = kMaxRings;
    for (int i = 0; i < n; ++i) {
        ThreadRing* r = __atomic_load_n(&g_rings[i], __ATOMIC_ACQUIRE);
        if (r != nullptr) total += r->next.load(std::memory_order_relaxed);
    }
    return total;
}

uint64_t TotalDropped() {
    uint64_t dropped = g_lost.load(std::memory_order_relaxed);
    int n = g_nrings.load(std::memory_order_acquire);
    if (n > kMaxRings) n = kMaxRings;
    for (int i = 0; i < n; ++i) {
        ThreadRing* r = __atomic_load_n(&g_rings[i], __ATOMIC_ACQUIRE);
        if (r == nullptr) continue;
        uint64_t next = r->next.load(std::memory_order_relaxed);
        if (next > r->cap) dropped += next - r->cap;
    }
    return dropped;
}

uint64_t RingHighwater() {
    uint64_t hw = 0;
    int n = g_nrings.load(std::memory_order_acquire);
    if (n > kMaxRings) n = kMaxRings;
    for (int i = 0; i < n; ++i) {
        ThreadRing* r = __atomic_load_n(&g_rings[i], __ATOMIC_ACQUIRE);
        if (r == nullptr) continue;
        uint64_t next = r->next.load(std::memory_order_relaxed);
        uint64_t valid = next < r->cap ? next : r->cap;
        if (valid > hw) hw = valid;
    }
    return hw;
}

uint64_t DumpCount() { return g_dump_count.load(std::memory_order_relaxed); }

void ExposeVars() {
    static std::atomic<bool> done{false};
    bool expected = false;
    if (!done.compare_exchange_strong(expected, true)) return;
    static PassiveStatus<int64_t> events(PassiveEvents, nullptr);
    static PassiveStatus<int64_t> dropped(PassiveDropped, nullptr);
    static PassiveStatus<int64_t> highwater(PassiveHighwater, nullptr);
    static PassiveStatus<int64_t> dumps(PassiveDumps, nullptr);
    events.expose("rpc_blackbox_events");
    dropped.expose("rpc_blackbox_dropped");
    highwater.expose("rpc_blackbox_ring_highwater");
    dumps.expose("rpc_flight_dump_count");
}

namespace {

// Keep g_on and g_crash_path in lockstep with their flags, including live
// mutation through the /flags portal. Runs at static init in this TU, after
// the flag objects above are constructed.
struct FlagHooks {
    FlagHooks() {
        g_on.store(FLAGS_flight_recorder_enabled.get(),
                   std::memory_order_relaxed);
        FLAGS_flight_recorder_enabled.set_on_change([] {
            g_on.store(FLAGS_flight_recorder_enabled.get(),
                       std::memory_order_relaxed);
        });
        FLAGS_flight_blackbox_path.set_on_change([] {
            std::string p = FLAGS_flight_blackbox_path.get();
            strncpy(g_crash_path, p.c_str(), sizeof(g_crash_path) - 1);
            g_crash_path[sizeof(g_crash_path) - 1] = '\0';
        });
    }
};
FlagHooks g_flag_hooks;

}  // namespace

}  // namespace flight
}  // namespace tpurpc
