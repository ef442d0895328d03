#include "tici/block_lease.h"

#include <atomic>
#include <chrono>
#include <map>
#include <mutex>
#include <thread>
#include <vector>

#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tvar/reducer.h"

DEFINE_int64(pool_lease_default_ms, 30000,
             "pin lifetime for pool-descriptor blocks whose RPC carries "
             "no deadline; the reaper reclaims older pins");
DEFINE_int64(pool_lease_grace_ms, 2000,
             "slack added to an RPC's propagated deadline before its "
             "pinned block is reapable (EndRPC normally releases first; "
             "the reaper is the backstop for wedged calls)");
DEFINE_int64(pool_lease_reap_ms, 200,
             "expiry-reaper scan interval for pinned pool blocks");

namespace tpurpc {
namespace block_lease {

namespace {

struct Lease {
    IOBuf pinned;        // the one ref keeping the slab slot alive
    // Ledger direction: "req" (client request pin, EndRPC releases) or
    // "rsp" (server response pin, the client's desc_ack releases).
    const char* direction = "req";
    uint64_t call_id = 0;
    // Always > 0: Pin stamps now + -pool_lease_default_ms so even a
    // lease whose owner dies before Arm is reapable (no unreapable
    // state exists); Arm tightens it to the RPC deadline + grace.
    int64_t deadline_us = 0;
    // Sockets whose peer may read this block. TWO slots: a backup
    // request leaves the original try in flight on another socket, so
    // the backup's arm ADDS its key; only when every entitled peer is
    // gone may peer-death reclamation free the pin (a retry, whose
    // previous try is finished, REPLACES instead).
    uint64_t peer_keys[2] = {kNoPeer, kNoPeer};
    int npeers = 0;
};

// Immortal singletons: Release runs from EndRPC, which Socket recycling
// can reach during static teardown (same rule as the peer-pool
// registry in shm_link.cc).
std::mutex& mu() {
    static std::mutex* m = new std::mutex;
    return *m;
}
std::map<uint64_t, Lease>& leases() {
    static auto* m = new std::map<uint64_t, Lease>;
    return *m;
}

std::atomic<uint64_t> g_next_id{1};
std::atomic<uint64_t> g_pinned{0};
std::atomic<uint64_t> g_pins_total{0};
std::atomic<uint64_t> g_released{0};
std::atomic<uint64_t> g_expired{0};
std::atomic<uint64_t> g_peer_released{0};

// rpc_pool_* observability (satellite): live pins as a passive gauge,
// reclamation paths as counters — the leak signature of a descriptor
// data path is "pinned_blocks grows while reaped stays 0".
int64_t read_pinned(void*) {
    return (int64_t)g_pinned.load(std::memory_order_relaxed);
}
struct GaugeExposer {
    GaugeExposer() {
        auto* g = new PassiveStatus<int64_t>(&read_pinned, nullptr);
        g->expose("rpc_pool_pinned_blocks");
    }
};
static LazyAdder g_var_expired("rpc_pool_lease_expired");
static LazyAdder g_var_reaped("rpc_pool_reaped");
static LazyAdder g_var_peer_released("rpc_pool_peer_released");

std::atomic<bool> g_reaper_started{false};

void ReaperLoop() {
    while (true) {
        int64_t interval = FLAGS_pool_lease_reap_ms.get();
        if (interval < 10) interval = 10;
        std::this_thread::sleep_for(std::chrono::milliseconds(interval));
        ReapExpired(monotonic_time_us());
    }
}

// Drop a lease's pin OUTSIDE the registry lock: the IOBuf release runs
// the block deallocator (slab recycle), which must never nest under
// this mutex (FreeSlab takes the class mutex; a resolver thread could
// hold it while calling into the registry).
void drop_pins(std::vector<IOBuf>* pins) { pins->clear(); }

}  // namespace

uint64_t Pin(IOBuf&& buf, const char* direction) {
    StartReaper();
    const uint64_t id =
        g_next_id.fetch_add(1, std::memory_order_relaxed);
    flight::Record(flight::kLeasePin, id, buf.size());
    {
        std::lock_guard<std::mutex> g(mu());
        Lease& l = leases()[id];
        l.pinned = std::move(buf);
        l.direction = direction;
        // Default lifetime from the moment of the pin: a lease whose
        // owner never reaches Arm (setup failure + dropped release) is
        // still reapable — no unreapable pin state exists.
        l.deadline_us = monotonic_time_us() +
                        FLAGS_pool_lease_default_ms.get() * 1000;
    }
    g_pinned.fetch_add(1, std::memory_order_relaxed);
    g_pins_total.fetch_add(1, std::memory_order_relaxed);
    return id;
}

bool Arm(uint64_t lease_id, uint64_t call_id, int64_t deadline_us,
         uint64_t peer_key, bool add_peer) {
    if (lease_id == 0) return false;
    const int64_t now = monotonic_time_us();
    int64_t expiry;
    if (deadline_us > 0) {
        expiry = deadline_us + FLAGS_pool_lease_grace_ms.get() * 1000;
    } else {
        expiry = now + FLAGS_pool_lease_default_ms.get() * 1000;
    }
    std::lock_guard<std::mutex> g(mu());
    auto it = leases().find(lease_id);
    if (it == leases().end()) return false;  // already reaped/released
    Lease& l = it->second;
    l.call_id = call_id;
    l.deadline_us = expiry;
    if (add_peer && l.npeers == 1 && l.peer_keys[0] != peer_key) {
        // Backup request: the original try's peer stays entitled to
        // read the block — hold BOTH keys.
        l.peer_keys[1] = peer_key;
        l.npeers = 2;
    } else {
        l.peer_keys[0] = peer_key;
        l.peer_keys[1] = kNoPeer;
        l.npeers = peer_key != kNoPeer ? 1 : 0;
    }
    flight::Record(flight::kLeaseArm, lease_id, call_id);
    return true;
}

bool Release(uint64_t lease_id) {
    if (lease_id == 0) return false;
    IOBuf pin;
    {
        std::lock_guard<std::mutex> g(mu());
        auto it = leases().find(lease_id);
        if (it == leases().end()) return false;
        pin = std::move(it->second.pinned);
        leases().erase(it);
    }
    g_pinned.fetch_sub(1, std::memory_order_relaxed);
    g_released.fetch_add(1, std::memory_order_relaxed);
    flight::Record(flight::kLeaseRelease, lease_id, pin.size());
    pin.clear();  // the dec_ref -> slab recycle, outside the lock
    return true;
}

bool Alive(uint64_t lease_id) {
    if (lease_id == 0) return false;
    std::lock_guard<std::mutex> g(mu());
    return leases().count(lease_id) != 0;
}

size_t ReapExpired(int64_t now_us) {
    std::vector<IOBuf> pins;
    {
        std::lock_guard<std::mutex> g(mu());
        auto& m = leases();
        for (auto it = m.begin(); it != m.end();) {
            if (it->second.deadline_us > 0 &&
                now_us >= it->second.deadline_us) {
                flight::Record(
                    flight::kLeaseExpire, it->first,
                    (uint64_t)((now_us - it->second.deadline_us) / 1000));
                pins.push_back(std::move(it->second.pinned));
                it = m.erase(it);
            } else {
                ++it;
            }
        }
    }
    const size_t n = pins.size();
    if (n > 0) {
        g_pinned.fetch_sub(n, std::memory_order_relaxed);
        g_expired.fetch_add(n, std::memory_order_relaxed);
        *g_var_expired << (int64_t)n;
        *g_var_reaped << (int64_t)n;
        LOG(WARNING) << "block_lease: reaped " << n
                     << " expired pinned pool block(s) (owner never "
                        "released — wedged call or leaked pin)";
        drop_pins(&pins);
    }
    return n;
}

size_t ReleasePeer(uint64_t peer_key) {
    if (peer_key == kNoPeer) return 0;
    std::vector<IOBuf> pins;
    {
        std::lock_guard<std::mutex> g(mu());
        auto& m = leases();
        for (auto it = m.begin(); it != m.end();) {
            Lease& l = it->second;
            bool held = false;
            for (int i = 0; i < l.npeers; ++i) {
                if (l.peer_keys[i] == peer_key) {
                    // Drop this peer's entitlement; compact.
                    l.peer_keys[i] = l.peer_keys[l.npeers - 1];
                    l.peer_keys[--l.npeers] = kNoPeer;
                    held = true;
                    break;
                }
            }
            if (held && l.npeers == 0) {
                // No surviving peer may read the block: reclaim. (With
                // a backup's second key still present — the original
                // try's server may be mid-read — the pin stays until
                // that peer dies too, EndRPC, or the lease expires.)
                pins.push_back(std::move(l.pinned));
                it = m.erase(it);
            } else {
                ++it;
            }
        }
    }
    const size_t n = pins.size();
    if (n > 0) {
        g_pinned.fetch_sub(n, std::memory_order_relaxed);
        g_peer_released.fetch_add(n, std::memory_order_relaxed);
        *g_var_peer_released << (int64_t)n;
        *g_var_reaped << (int64_t)n;
        flight::Record(flight::kLeasePeerDeath, peer_key, n);
        drop_pins(&pins);
    }
    return n;
}

size_t ReleaseByCall(uint64_t call_id, uint64_t peer_key) {
    if (call_id == 0) return 0;
    std::vector<IOBuf> pins;
    {
        std::lock_guard<std::mutex> g(mu());
        auto& m = leases();
        for (auto it = m.begin(); it != m.end();) {
            Lease& l = it->second;
            bool entitled = false;
            for (int i = 0; i < l.npeers; ++i) {
                entitled = entitled || l.peer_keys[i] == peer_key;
            }
            if (l.call_id == call_id && entitled) {
                pins.push_back(std::move(l.pinned));
                it = m.erase(it);
            } else {
                ++it;
            }
        }
    }
    const size_t n = pins.size();
    if (n > 0) {
        g_pinned.fetch_sub(n, std::memory_order_relaxed);
        g_released.fetch_add(n, std::memory_order_relaxed);
        drop_pins(&pins);
    }
    return n;
}

bool ReleaseAcked(uint64_t lease_id, uint64_t call_id,
                  uint64_t peer_key) {
    if (lease_id == 0 || call_id == 0) return false;
    IOBuf pin;
    {
        std::lock_guard<std::mutex> g(mu());
        auto it = leases().find(lease_id);
        if (it == leases().end()) return false;  // already released
        Lease& l = it->second;
        bool entitled = false;
        for (int i = 0; i < l.npeers; ++i) {
            entitled = entitled || l.peer_keys[i] == peer_key;
        }
        if (l.call_id != call_id || !entitled) return false;
        pin = std::move(l.pinned);
        leases().erase(it);
    }
    g_pinned.fetch_sub(1, std::memory_order_relaxed);
    g_released.fetch_add(1, std::memory_order_relaxed);
    flight::Record(flight::kLeaseRelease, lease_id, pin.size());
    pin.clear();  // dec_ref -> slab recycle, outside the lock
    return true;
}

uint64_t pinned() { return g_pinned.load(std::memory_order_relaxed); }
uint64_t pins_total() {
    return g_pins_total.load(std::memory_order_relaxed);
}
uint64_t released() { return g_released.load(std::memory_order_relaxed); }
uint64_t expired_reaped() {
    return g_expired.load(std::memory_order_relaxed);
}
uint64_t peer_released() {
    return g_peer_released.load(std::memory_order_relaxed);
}

std::string DebugString() {
    char line[160];
    std::string out;
    snprintf(line, sizeof(line), "pinned %llu\n",
             (unsigned long long)pinned());
    out += line;
    snprintf(line, sizeof(line), "pins_total %llu\n",
             (unsigned long long)pins_total());
    out += line;
    snprintf(line, sizeof(line), "released %llu\n",
             (unsigned long long)released());
    out += line;
    snprintf(line, sizeof(line), "lease_expired %llu\n",
             (unsigned long long)expired_reaped());
    out += line;
    snprintf(line, sizeof(line), "peer_released %llu\n",
             (unsigned long long)peer_released());
    out += line;
    const int64_t now = monotonic_time_us();
    std::lock_guard<std::mutex> g(mu());
    int shown = 0;
    for (const auto& kv : leases()) {
        if (++shown > 64) {
            out += "...\n";
            break;
        }
        const Lease& l = kv.second;
        snprintf(line, sizeof(line),
                 "lease %llu dir=%s bytes=%zu call=%llu "
                 "deadline_in_ms=%lld peer=%lld peer2=%lld\n",
                 (unsigned long long)kv.first, l.direction,
                 l.pinned.size(), (unsigned long long)l.call_id,
                 (long long)((l.deadline_us - now) / 1000),
                 (long long)l.peer_keys[0], (long long)l.peer_keys[1]);
        out += line;
    }
    return out;
}

std::string JsonLeases(size_t max) {
    const int64_t now = monotonic_time_us();
    std::string out = "[";
    char line[192];
    std::lock_guard<std::mutex> g(mu());
    size_t shown = 0;
    for (const auto& kv : leases()) {
        if (shown >= max) break;
        const Lease& l = kv.second;
        snprintf(line, sizeof(line),
                 "%s{\"id\": %llu, \"direction\": \"%s\", \"bytes\": %zu, "
                 "\"call\": %llu, \"deadline_in_ms\": %lld, "
                 "\"peer\": %lld}",
                 shown == 0 ? "" : ", ", (unsigned long long)kv.first,
                 l.direction, l.pinned.size(),
                 (unsigned long long)l.call_id,
                 (long long)((l.deadline_us - now) / 1000),
                 (long long)l.peer_keys[0]);
        out += line;
        ++shown;
    }
    out += "]";
    return out;
}

void ExposeVars() {
    static std::atomic<bool> done{false};
    if (done.exchange(true, std::memory_order_acq_rel)) return;
    static GaugeExposer expose_gauge;
    // Touch the lazy adders so the families exist in /metrics from the
    // first scrape (a 0-valued counter is data; a missing one is not).
    *g_var_expired << 0;
    *g_var_reaped << 0;
    *g_var_peer_released << 0;
}

void StartReaper() {
    if (g_reaper_started.exchange(true, std::memory_order_acq_rel)) {
        return;
    }
    ExposeVars();
    std::thread(ReaperLoop).detach();
}

}  // namespace block_lease
}  // namespace tpurpc
