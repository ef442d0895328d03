#include "tici/block_pool.h"

#include <fcntl.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <map>
#include <mutex>
#include <vector>

#include "tbase/iobuf.h"
#include "tbase/logging.h"
#include "tbase/fast_rand.h"
#include "tnet/fault_injection.h"
#include "tnet/transport.h"

namespace tpurpc {

namespace {

struct Region {
    char* base;
    size_t size;
};

struct PoolState {
    std::mutex mu;
    std::vector<Region> regions;   // [0] is the shared primary (if any)
    // Freed default-size blocks, partitioned by transferability: blocks
    // inside the shared primary can be posted to peers zero-copy and are
    // preferred on allocation (keeps the zero-copy rate high after the
    // pool has ever overflowed into anonymous regions).
    std::vector<void*> freelist_shared;
    std::vector<void*> freelist_other;
    // Bounce reserve: the TAIL of the primary is carved exclusively by
    // AllocateSharedBlock, with its own freelist — general traffic must
    // not be able to strand the cross-process copy path's memory in
    // per-thread caches (bounce blocks themselves bypass caches via the
    // DeallocateShared dealloc pointer, so this band self-recycles).
    std::vector<void*> freelist_bounce;
    size_t bounce_reserve = 8u << 20;
    size_t bounce_carve = 0;  // into the reserved band
    size_t region_step = 64u << 20;
    size_t carve_offset = 0;       // into regions.back()
    std::atomic<size_t> live{0};
    std::atomic<bool> inited{false};
    char shm_name[64] = "";
    char* shm_base = nullptr;
    size_t shm_size = 0;

    bool in_shared(const void* ptr) const {
        const char* c = (const char*)ptr;
        return shm_base != nullptr && c >= shm_base && c < shm_base + shm_size;
    }
    bool in_bounce_band(const void* ptr) const {
        const char* c = (const char*)ptr;
        return shm_base != nullptr &&
               c >= shm_base + (shm_size - bounce_reserve) &&
               c < shm_base + shm_size;
    }
    // General carve limit within the CURRENT back region.
    size_t carve_limit() const {
        const Region& r = regions.back();
        return r.base == shm_base ? r.size - bounce_reserve : r.size;
    }
};

PoolState& pool() {
    static PoolState p;
    return p;
}

// Cross-process pressure: set when AllocateSharedBlock runs dry; while
// set, dec_ref routes SHARED-region blocks straight back to the pool
// (IOBuf::blockmem_cache_veto) instead of per-thread caches, refilling
// freelist_shared until the watermark clears it. Keeps the hot path at
// one relaxed load when the shm transport isn't starved.
std::atomic<bool> g_shared_pressure{false};
constexpr size_t kSharedRefillWatermark = 256;

bool shared_cache_veto(const void* p) {
    return g_shared_pressure.load(std::memory_order_relaxed) &&
           pool().in_shared(p);
}

void unlink_shm_at_exit() {
    PoolState& p = pool();
    if (p.shm_name[0] != '\0') shm_unlink(p.shm_name);
}

// Create the primary region as a named POSIX shm segment so peers can map
// it (the "memory registration" of this transport). Returns false on any
// failure; caller falls back to an anonymous region. Caller holds mu.
bool create_shared_primary_locked(PoolState& p) {
    snprintf(p.shm_name, sizeof(p.shm_name), "/tpurpc_pool_%d_%08lx",
             (int)getpid(), (unsigned long)fast_rand());
    const int fd = shm_open(p.shm_name, O_CREAT | O_EXCL | O_RDWR, 0600);
    if (fd < 0) {
        PLOG(WARNING) << "IciBlockPool: shm_open " << p.shm_name
                      << " failed; pool is process-local";
        p.shm_name[0] = '\0';
        return false;
    }
    if (ftruncate(fd, (off_t)p.region_step) != 0) {
        PLOG(ERROR) << "IciBlockPool: ftruncate failed";
        close(fd);
        shm_unlink(p.shm_name);
        p.shm_name[0] = '\0';
        return false;
    }
    void* mem = mmap(nullptr, p.region_step, PROT_READ | PROT_WRITE,
                     MAP_SHARED, fd, 0);
    close(fd);  // the mapping keeps the segment alive
    if (mem == MAP_FAILED) {
        PLOG(ERROR) << "IciBlockPool: mmap shared primary failed";
        shm_unlink(p.shm_name);
        p.shm_name[0] = '\0';
        return false;
    }
    p.shm_base = (char*)mem;
    p.shm_size = p.region_step;
    p.regions.push_back(Region{(char*)mem, p.region_step});
    p.carve_offset = 0;
    // The name must outlive process setup so late-connecting peers can
    // map it; unlink on orderly exit (a crash leaves a /dev/shm entry the
    // next Init from the same pid range won't collide with — names embed
    // pid+random).
    atexit(unlink_shm_at_exit);
    return true;
}

// mmap one more (anonymous, process-local) region. Caller holds mu.
bool grow_locked(PoolState& p) {
    void* mem = mmap(nullptr, p.region_step, PROT_READ | PROT_WRITE,
                     MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
    if (mem == MAP_FAILED) {
        PLOG(ERROR) << "IciBlockPool: mmap " << p.region_step << " failed";
        return false;
    }
    p.regions.push_back(Region{(char*)mem, p.region_step});
    p.carve_offset = 0;
    return true;
}

}  // namespace

void* IciBlockPool::Allocate(size_t n) {
    PoolState& p = pool();
    if (n == IOBuf::DEFAULT_BLOCK_SIZE) {
        std::lock_guard<std::mutex> g(p.mu);
        if (!p.freelist_shared.empty()) {
            void* b = p.freelist_shared.back();
            p.freelist_shared.pop_back();
            p.live.fetch_add(1, std::memory_order_relaxed);
            return b;
        }
        if (!p.freelist_other.empty()) {
            void* b = p.freelist_other.back();
            p.freelist_other.pop_back();
            p.live.fetch_add(1, std::memory_order_relaxed);
            return b;
        }
        if (p.regions.empty() || p.carve_offset + n > p.carve_limit()) {
            if (!grow_locked(p)) return nullptr;
        }
        void* b = p.regions.back().base + p.carve_offset;
        p.carve_offset += n;
        p.live.fetch_add(1, std::memory_order_relaxed);
        return b;
    }
    // Odd-size block: plain malloc, tagged so Deallocate can tell it from
    // a pool block (a real libtpu build would register these mappings on
    // demand; the send path bounce-copies them into pool blocks).
    void* mem = malloc(n);
    return mem;
}

void IciBlockPool::Deallocate(void* b) {
    PoolState& p = pool();
    {
        std::lock_guard<std::mutex> g(p.mu);
        const char* c = (const char*)b;
        for (const Region& r : p.regions) {
            if (c >= r.base && c < r.base + r.size) {
                if (p.in_bounce_band(b)) {
                    p.freelist_bounce.push_back(b);
                } else if (p.in_shared(b)) {
                    p.freelist_shared.push_back(b);
                    if (p.freelist_shared.size() >= kSharedRefillWatermark) {
                        g_shared_pressure.store(
                            false, std::memory_order_relaxed);
                    }
                } else {
                    p.freelist_other.push_back(b);
                }
                p.live.fetch_sub(1, std::memory_order_relaxed);
                return;
            }
        }
    }
    free(b);  // odd-size malloc'd block
}

void IciBlockPool::DeallocateShared(void* p) { Deallocate(p); }

void* IciBlockPool::AllocateSharedBlock() {
    PoolState& p = pool();
    std::lock_guard<std::mutex> g(p.mu);
    if (p.shm_base == nullptr) return nullptr;
    // A successful allocation means starvation is over: unlatch the
    // pressure flag here (the freelist watermark alone is unreachable
    // for small pools, and a latched flag would disable the TLS block
    // caches forever).
    g_shared_pressure.store(false, std::memory_order_relaxed);
    // The reserved band first: its blocks recycle through
    // freelist_bounce only (never via per-thread caches), so the bounce
    // path can't be starved by general traffic. In-flight bounce data
    // is bounded by the descriptor rings (kDepth slots x 8KB per pipe),
    // so the reserve covers the bounce workload structurally; the
    // pressure fallback below is belt-and-braces for many-link setups.
    if (!p.freelist_bounce.empty()) {
        void* b = p.freelist_bounce.back();
        p.freelist_bounce.pop_back();
        p.live.fetch_add(1, std::memory_order_relaxed);
        return b;
    }
    if (p.bounce_carve + IOBuf::DEFAULT_BLOCK_SIZE <= p.bounce_reserve) {
        void* b =
            p.shm_base + (p.shm_size - p.bounce_reserve) + p.bounce_carve;
        p.bounce_carve += IOBuf::DEFAULT_BLOCK_SIZE;
        p.live.fetch_add(1, std::memory_order_relaxed);
        return b;
    }
    // Reserve exhausted (more than 8MB of bounce data in flight): fall
    // back to the general shared freelist / carve.
    if (!p.freelist_shared.empty()) {
        void* b = p.freelist_shared.back();
        p.freelist_shared.pop_back();
        p.live.fetch_add(1, std::memory_order_relaxed);
        return b;
    }
    if (!p.regions.empty() && p.regions.back().base == p.shm_base &&
        p.carve_offset + IOBuf::DEFAULT_BLOCK_SIZE <= p.carve_limit()) {
        void* b = p.regions.back().base + p.carve_offset;
        p.carve_offset += IOBuf::DEFAULT_BLOCK_SIZE;
        p.live.fetch_add(1, std::memory_order_relaxed);
        return b;
    }
    // Dry: shared blocks are circulating in per-thread caches. Raise the
    // pressure flag so dec_ref routes them back here; callers retry.
    g_shared_pressure.store(true, std::memory_order_relaxed);
    return nullptr;
}

void* IciBlockPool::AllocateRegistered(size_t n) {
    PoolState& p = pool();
    std::lock_guard<std::mutex> g(p.mu);
    if (p.regions.empty()) return nullptr;
    n = (n + 4095) & ~(size_t)4095;  // page-align carve for DMA
    if (n > p.region_step) {
        // One-off oversized region of its own.
        void* mem = mmap(nullptr, n, PROT_READ | PROT_WRITE,
                         MAP_PRIVATE | MAP_ANONYMOUS, -1, 0);
        if (mem == MAP_FAILED) return nullptr;
        p.regions.push_back(Region{(char*)mem, n});
        // Keep the carve pointer on the PREVIOUS region: this one is
        // fully consumed by the chunk.
        std::swap(p.regions[p.regions.size() - 2],
                  p.regions[p.regions.size() - 1]);
        return mem;
    }
    if (p.carve_offset + n > p.carve_limit()) {
        if (!grow_locked(p)) return nullptr;
    }
    void* b = p.regions.back().base + p.carve_offset;
    p.carve_offset += n;
    return b;
}

bool IciBlockPool::Contains(const void* ptr) {
    PoolState& p = pool();
    std::lock_guard<std::mutex> g(p.mu);
    const char* c = (const char*)ptr;
    for (const Region& r : p.regions) {
        if (c >= r.base && c < r.base + r.size) return true;
    }
    return false;
}

const char* IciBlockPool::shm_name() { return pool().shm_name; }
size_t IciBlockPool::shm_size() { return pool().shm_size; }
char* IciBlockPool::shm_base() { return pool().shm_base; }

bool IciBlockPool::OffsetOf(const void* ptr, uint64_t* offset) {
    PoolState& p = pool();
    // shm_base/shm_size are written once under Init's mu and read-only
    // after; no lock needed on this hot path.
    const char* c = (const char*)ptr;
    if (p.shm_base == nullptr || c < p.shm_base ||
        c >= p.shm_base + p.shm_size) {
        return false;
    }
    *offset = (uint64_t)(c - p.shm_base);
    return true;
}

// ---------------- slab-class registered allocator (ISSUE 9c) ----------------

namespace {

// Size classes: 8K covers descriptor/meta staging, 1M the default device
// chunk, 4M jumbo chunks. Arena size is chosen so one carve amortizes
// ~16 slots of the class (one central-mutex touch per 16 allocations
// even with a cold cache).
constexpr size_t kSlabClassBytes[] = {8u << 10, 64u << 10, 256u << 10,
                                      1u << 20, 4u << 20};
constexpr int kSlabClasses =
    (int)(sizeof(kSlabClassBytes) / sizeof(kSlabClassBytes[0]));
constexpr int kTlsSlotsPerClass = 8;
// A thread parks at most this many BYTES of freed slots per class, so the
// 1M/4M classes never park: slots freed on a thread that does not
// allocate them (attachments released on fiber workers) would otherwise
// strand — workers x 8 x 1MiB outgrows the 64MiB shm region, the next
// arena lands in anonymous overflow and OffsetOf refuses it.
constexpr size_t kTlsBytesPerClass = 512u << 10;
constexpr int tls_slots_of(int cls) {
    return (int)std::min<size_t>(kTlsSlotsPerClass,
                                 kTlsBytesPerClass / kSlabClassBytes[cls]);
}

// One registered arena, chopped into slots of a single class. The arena
// table is append-only and scanned lock-free (count published with
// release/acquire) — FreeSlab derives the class of a pointer from it on
// every TLS-cache overflow without touching any mutex.
struct SlabArena {
    char* base;
    size_t size;
    int cls;
};
SlabArena g_arenas[256];
std::atomic<uint32_t> g_arena_count{0};
// Serializes appends only (two CLASSES can grow arenas concurrently
// under their own class mutexes); readers stay lock-free.
std::mutex g_arena_append_mu;

// Per-class central state: freelist + carve cursor, each class behind
// its OWN mutex so concurrent traffic in different classes never
// serializes (and same-class traffic mostly stays in the TLS cache).
struct SlabClass {
    std::mutex mu;
    std::vector<void*> freelist;
    char* carve_base = nullptr;
    size_t carve_off = 0;
    size_t carve_size = 0;
};
SlabClass& slab_class(int cls) {
    static SlabClass* classes = new SlabClass[kSlabClasses];
    return classes[cls];
}

std::atomic<size_t> g_slab_live{0};
std::atomic<size_t> g_slab_recycled{0};
std::atomic<size_t> g_slab_mutex_acquisitions{0};
// Per-class occupancy for /pools (relaxed: diagnostic, not invariant).
std::atomic<size_t> g_class_live[kSlabClasses] = {};
std::atomic<size_t> g_class_carved[kSlabClasses] = {};

int slab_class_of(size_t n) {
    for (int c = 0; c < kSlabClasses; ++c) {
        if (n <= kSlabClassBytes[c]) return c;
    }
    return -1;
}

int arena_class_of(const void* p) {
    const uint32_t count = g_arena_count.load(std::memory_order_acquire);
    const char* c = (const char*)p;
    for (uint32_t i = 0; i < count; ++i) {
        if (c >= g_arenas[i].base && c < g_arenas[i].base + g_arenas[i].size) {
            return g_arenas[i].cls;
        }
    }
    return -1;
}

// Per-thread slot cache. On thread exit the destructor drains every
// cached slot back to its class freelist so no registered memory is
// stranded in dead threads.
struct TlsSlabCache {
    void* slots[kSlabClasses][kTlsSlotsPerClass];
    int n[kSlabClasses] = {};

    ~TlsSlabCache() {
        for (int c = 0; c < kSlabClasses; ++c) {
            if (n[c] == 0) continue;
            SlabClass& sc = slab_class(c);
            g_slab_mutex_acquisitions.fetch_add(1,
                                                std::memory_order_relaxed);
            std::lock_guard<std::mutex> g(sc.mu);
            for (int i = 0; i < n[c]; ++i) sc.freelist.push_back(slots[c][i]);
            n[c] = 0;
        }
    }
};
thread_local TlsSlabCache g_tls_slabs;

}  // namespace

int IciBlockPool::SlabClassOf(size_t n) { return slab_class_of(n); }
size_t IciBlockPool::slab_class_bytes(int cls) {
    return cls >= 0 && cls < kSlabClasses ? kSlabClassBytes[cls] : 0;
}
size_t IciBlockPool::slab_allocated() {
    return g_slab_live.load(std::memory_order_relaxed);
}
size_t IciBlockPool::slab_recycled() {
    return g_slab_recycled.load(std::memory_order_relaxed);
}
size_t IciBlockPool::slab_mutex_acquisitions() {
    return g_slab_mutex_acquisitions.load(std::memory_order_relaxed);
}

IciBlockPool::SlabClassStat IciBlockPool::slab_class_stat(int cls) {
    SlabClassStat st;
    if (cls < 0 || cls >= kSlabClasses) return st;
    st.live = g_class_live[cls].load(std::memory_order_relaxed);
    st.carved = g_class_carved[cls].load(std::memory_order_relaxed);
    SlabClass& sc = slab_class(cls);
    std::lock_guard<std::mutex> g(sc.mu);
    st.freelist = sc.freelist.size();
    return st;
}

void* IciBlockPool::AllocateSlab(size_t n) {
    const int cls = slab_class_of(n);
    if (cls < 0) {
        // Above the largest class: one-off registered carve (no recycle).
        return AllocateRegistered(n);
    }
    // 1. TLS cache: the steady-state path, no locks at all.
    TlsSlabCache& tls = g_tls_slabs;
    if (tls.n[cls] > 0) {
        void* p = tls.slots[cls][--tls.n[cls]];
        g_slab_live.fetch_add(1, std::memory_order_relaxed);
        g_class_live[cls].fetch_add(1, std::memory_order_relaxed);
        g_slab_recycled.fetch_add(1, std::memory_order_relaxed);
        return p;
    }
    // 2. Class freelist / arena carve under the CLASS mutex.
    SlabClass& sc = slab_class(cls);
    g_slab_mutex_acquisitions.fetch_add(1, std::memory_order_relaxed);
    std::lock_guard<std::mutex> g(sc.mu);
    if (!sc.freelist.empty()) {
        void* p = sc.freelist.back();
        sc.freelist.pop_back();
        g_slab_live.fetch_add(1, std::memory_order_relaxed);
        g_class_live[cls].fetch_add(1, std::memory_order_relaxed);
        g_slab_recycled.fetch_add(1, std::memory_order_relaxed);
        return p;
    }
    const size_t slot = kSlabClassBytes[cls];
    if (sc.carve_base == nullptr || sc.carve_off + slot > sc.carve_size) {
        // New arena: a large aligned registered slab (~16 slots, min 1
        // region-friendly chunk) carved from the pool's regions, then
        // published append-only for lock-free class lookup. Capped at
        // 16MB: the jumbo classes must still fit INSIDE the shm region
        // (a 4MB-class x16 arena would be the whole 64MB pool, land in
        // an anonymous overflow region, and silently disqualify every
        // jumbo slot from descriptor/verb-window use forever).
        const size_t arena_bytes =
            std::min<size_t>(slot * 16,
                             std::max<size_t>(slot, (size_t)16 << 20));
        char* base = (char*)AllocateRegistered(arena_bytes);
        if (base == nullptr) return nullptr;
        {
            std::lock_guard<std::mutex> ag(g_arena_append_mu);
            const uint32_t idx =
                g_arena_count.load(std::memory_order_relaxed);
            if (idx < sizeof(g_arenas) / sizeof(g_arenas[0])) {
                g_arenas[idx] = SlabArena{base, arena_bytes, cls};
                g_arena_count.store(idx + 1, std::memory_order_release);
            } else {
                // Lookup table full: still carve from this arena (the
                // memory is valid registered pool) — its slots just
                // won't recycle (FreeSlab can't classify them), which
                // beats leaking a full arena per cache miss forever.
                LOG_EVERY_N(ERROR, 1000)
                    << "IciBlockPool: slab arena table full; class "
                    << cls << " slots from this arena will not recycle";
            }
        }
        sc.carve_base = base;
        sc.carve_off = 0;
        sc.carve_size = arena_bytes;
    }
    void* p = sc.carve_base + sc.carve_off;
    sc.carve_off += slot;
    g_slab_live.fetch_add(1, std::memory_order_relaxed);
    g_class_live[cls].fetch_add(1, std::memory_order_relaxed);
    g_class_carved[cls].fetch_add(1, std::memory_order_relaxed);
    return p;
}

void IciBlockPool::FreeSlab(void* p) {
    if (p == nullptr) return;
    const int cls = arena_class_of(p);
    if (cls < 0) return;  // oversized/non-slab carve: process lifetime
    g_slab_live.fetch_sub(1, std::memory_order_relaxed);
    g_class_live[cls].fetch_sub(1, std::memory_order_relaxed);
    TlsSlabCache& tls = g_tls_slabs;
    if (tls.n[cls] < tls_slots_of(cls)) {
        tls.slots[cls][tls.n[cls]++] = p;
        return;
    }
    SlabClass& sc = slab_class(cls);
    g_slab_mutex_acquisitions.fetch_add(1, std::memory_order_relaxed);
    std::lock_guard<std::mutex> g(sc.mu);
    sc.freelist.push_back(p);
}

bool IciBlockPool::AllocatePoolAttachment(size_t n, IOBuf* out,
                                          char** data) {
    const size_t total = n + offsetof(IOBuf::Block, data);
    const int cls = slab_class_of(total);
    if (cls < 0) return false;
    void* mem = AllocateSlab(total);
    if (mem == nullptr) return false;
    uint64_t off = 0;
    if (!OffsetOf(mem, &off)) {
        // Slab arena landed in an overflow (non-shared) region: not
        // descriptor-eligible. Recycle and let the caller fall back.
        FreeSlab(mem);
        return false;
    }
    auto* b = new (mem) IOBuf::Block;
    b->nshared.store(1, std::memory_order_relaxed);
    b->size = (uint32_t)n;
    b->cap = (uint32_t)(kSlabClassBytes[cls] -
                        offsetof(IOBuf::Block, data));
    b->portal_next = nullptr;
    // Custom deallocator: the last dec_ref recycles the slot into its
    // slab class (never the TLS block cache — dealloc differs from the
    // installed pair, so dec_ref frees directly through it).
    b->dealloc = &IciBlockPool::FreeSlab;
    IOBuf::BlockRef ref;
    ref.offset = 0;
    ref.length = (uint32_t)n;
    ref.block = b;
    out->clear();
    // append_ref takes its own reference; drop ours so the IOBuf holds
    // the only one and its release recycles the slot.
    out->append_ref(ref);
    b->dec_ref();
    *data = b->data;
    return true;
}

bool IciBlockPool::AllocatePoolAttachmentCopy(const void* src, size_t n,
                                              IOBuf* out) {
    IOBuf buf;
    char* data = nullptr;
    if (!AllocatePoolAttachment(n, &buf, &data)) return false;
    memcpy(data, src, n);
    out->swap(buf);
    return true;
}

// ---------------- pool registry (ISSUE 9b) ----------------

namespace pool_registry {

namespace {
struct Mapping {
    const char* base;
    size_t size;
    uint64_t epoch;
};
// Immortal (same teardown-order rationale as the shm_link peer-pool
// registry: resolution can run from Socket recycling during exit).
std::mutex& reg_mu() {
    static std::mutex* mu = new std::mutex;
    return *mu;
}
std::map<uint64_t, Mapping>& reg() {
    static auto* m = new std::map<uint64_t, Mapping>;
    return *m;
}
std::atomic<uint64_t> g_resolves{0};
std::atomic<uint64_t> g_resolve_failures{0};
// id -> shm name (ISSUE 18): kept apart from the mapping table — it
// survives Unregister so a verbs re-grant after link churn can still
// locate the segment for a writable remap.
std::map<uint64_t, std::string>& name_reg() {
    static auto* m = new std::map<uint64_t, std::string>;
    return *m;
}
}  // namespace

uint64_t IdFromName(const char* name) {
    uint64_t h = 1469598103934665603ull;  // FNV-1a 64
    for (const char* c = name; *c != '\0'; ++c) {
        h ^= (uint64_t)(unsigned char)*c;
        h *= 1099511628211ull;
    }
    return h != 0 ? h : 1;  // 0 is reserved for "no pool"
}

void Register(uint64_t id, const char* base, size_t size,
              uint64_t epoch) {
    if (id == 0 || base == nullptr) return;
    std::lock_guard<std::mutex> g(reg_mu());
    reg()[id] = Mapping{base, size, epoch != 0 ? epoch : 1};
}

void Unregister(uint64_t id) {
    std::lock_guard<std::mutex> g(reg_mu());
    reg().erase(id);
}

void SetEpoch(uint64_t id, uint64_t epoch) {
    std::lock_guard<std::mutex> g(reg_mu());
    auto it = reg().find(id);
    if (it != reg().end()) it->second.epoch = epoch != 0 ? epoch : 1;
}

void RaiseEpoch(uint64_t id, uint64_t epoch) {
    std::lock_guard<std::mutex> g(reg_mu());
    auto it = reg().find(id);
    if (it != reg().end() && epoch > it->second.epoch) {
        it->second.epoch = epoch;
    }
}

bool Resolve(uint64_t id, const char** base, size_t* size,
             uint64_t* epoch) {
    std::lock_guard<std::mutex> g(reg_mu());
    auto it = reg().find(id);
    if (it == reg().end()) {
        g_resolve_failures.fetch_add(1, std::memory_order_relaxed);
        return false;
    }
    g_resolves.fetch_add(1, std::memory_order_relaxed);
    *base = it->second.base;
    *size = it->second.size;
    if (epoch != nullptr) *epoch = it->second.epoch;
    return true;
}

std::string DebugString() {
    std::string out;
    char line[128];
    std::lock_guard<std::mutex> g(reg_mu());
    for (const auto& kv : reg()) {
        snprintf(line, sizeof(line),
                 "pool %llu size=%zu epoch=%llu local=%d\n",
                 (unsigned long long)kv.first, kv.second.size,
                 (unsigned long long)kv.second.epoch,
                 kv.first == IciBlockPool::pool_id() ? 1 : 0);
        out += line;
    }
    return out;
}

void SetName(uint64_t id, const char* name) {
    if (id == 0 || name == nullptr || name[0] == '\0') return;
    std::lock_guard<std::mutex> g(reg_mu());
    name_reg()[id] = name;
}

bool NameOf(uint64_t id, char* buf, size_t n) {
    if (buf == nullptr || n == 0) return false;
    std::lock_guard<std::mutex> g(reg_mu());
    auto it = name_reg().find(id);
    if (it == name_reg().end() || it->second.size() + 1 > n) return false;
    memcpy(buf, it->second.c_str(), it->second.size() + 1);
    return true;
}

uint64_t resolves() { return g_resolves.load(std::memory_order_relaxed); }
uint64_t resolve_failures() {
    return g_resolve_failures.load(std::memory_order_relaxed);
}

}  // namespace pool_registry

uint64_t IciBlockPool::pool_id() {
    PoolState& p = pool();
    if (p.shm_name[0] == '\0') return 0;
    return pool_registry::IdFromName(p.shm_name);
}

// ---------------- epoch fencing (ISSUE 10b) ----------------

namespace {
// 1 once the pool exists; bumped on remap/restart events. A descriptor
// minted under epoch N is only honored while the mapping is at N.
std::atomic<uint64_t> g_pool_epoch{1};
}  // namespace

uint64_t IciBlockPool::pool_epoch() {
    return g_pool_epoch.load(std::memory_order_acquire);
}

uint64_t IciBlockPool::BumpEpoch() {
    const uint64_t e =
        g_pool_epoch.fetch_add(1, std::memory_order_acq_rel) + 1;
    // Keep the in-process registry honest: handlers resolving our OWN
    // descriptors (loopback links) must see the new generation too.
    const uint64_t id = pool_id();
    if (id != 0) pool_registry::SetEpoch(id, e);
    return e;
}

// ---------------- device staging ring (ISSUE 9a) ----------------

namespace {
struct RingSync {
    std::mutex mu;
    std::condition_variable cv;
};
}  // namespace

DeviceStagingRing* DeviceStagingRing::Create(uint32_t depth,
                                             size_t slot_bytes) {
    if (depth == 0 || depth > 1024 || slot_bytes == 0) return nullptr;
    auto* r = new DeviceStagingRing;
    r->depth_ = depth;
    r->slot_bytes_ = slot_bytes;
    r->slots_ = new char*[depth];
    r->slot_kind_ = new uint8_t[depth]();
    r->done_ = new bool[depth]();
    r->mu_ = new RingSync;
    r->registered_ = true;
    const bool slab_sized = IciBlockPool::SlabClassOf(slot_bytes) >= 0;
    for (uint32_t i = 0; i < depth; ++i) {
        char* s = (char*)IciBlockPool::AllocateSlab(slot_bytes);
        uint8_t kind = slab_sized ? 0 : 2;  // slab vs carve-only chunk
        if (s == nullptr) {
            // Pool dry/uninitialized: plain aligned memory keeps the ring
            // usable (the benchmark reports registered=false honestly).
            s = (char*)aligned_alloc(4096, (slot_bytes + 4095) & ~4095ul);
            kind = 1;
        }
        if (s == nullptr) {
            r->depth_ = i;  // free only what was built
            delete r;
            return nullptr;
        }
        r->slots_[i] = s;
        r->slot_kind_[i] = kind;
        r->registered_ = r->registered_ && IciBlockPool::Contains(s);
    }
    return r;
}

DeviceStagingRing::~DeviceStagingRing() {
    for (uint32_t i = 0; i < depth_; ++i) {
        switch (slot_kind_[i]) {
            case 0:
                IciBlockPool::FreeSlab(slots_[i]);
                break;
            case 1:
                free(slots_[i]);
                break;
            default:
                break;  // carve-only registered chunk: process lifetime
        }
    }
    delete[] slots_;
    delete[] slot_kind_;
    delete[] done_;
    delete (RingSync*)mu_;
}

int DeviceStagingRing::Acquire(int64_t timeout_us) {
    RingSync* sync = (RingSync*)mu_;
    std::unique_lock<std::mutex> lk(sync->mu);
    // Wake on EITHER a free slot or an abort: a poisoned ring (device
    // stream error, shutdown) must unblock parked Python threads
    // immediately instead of letting them wedge to their timeout.
    const auto ready = [this] {
        return aborted_.load(std::memory_order_relaxed) ||
               head_.load(std::memory_order_relaxed) -
                       tail_.load(std::memory_order_relaxed) <
                   depth_;
    };
    if (timeout_us < 0) {
        sync->cv.wait(lk, ready);
    } else if (!sync->cv.wait_for(lk, std::chrono::microseconds(timeout_us),
                                  ready)) {
        return -1;
    }
    if (aborted_.load(std::memory_order_relaxed)) {
        return -2;
    }
    const uint64_t seq = head_.fetch_add(1, std::memory_order_relaxed);
    const uint32_t inflight =
        (uint32_t)(seq + 1 - tail_.load(std::memory_order_relaxed));
    if (inflight > highwater_.load(std::memory_order_relaxed)) {
        highwater_.store(inflight, std::memory_order_relaxed);
    }
    return (int)(seq % depth_);
}

void DeviceStagingRing::Abort() {
    RingSync* sync = (RingSync*)mu_;
    {
        std::lock_guard<std::mutex> lk(sync->mu);
        aborted_.store(true, std::memory_order_release);
    }
    sync->cv.notify_all();
}

int DeviceStagingRing::Complete(uint32_t slot) {
    // Chaos seam (chaos_pool, ISSUE 10d): a delayed or dropped device
    // completion — the ring analog of a lost DMA interrupt. Decided
    // OUTSIDE the ring mutex; plain usleep, this path runs on Python /
    // driver threads, never fibers. A dropped complete leaves the
    // window stuck: Acquire's timeout (or Abort) is the proven escape.
    if (__builtin_expect(fault_injection_enabled(), 0)) {
        const FaultAction fault =
            FaultInjection::Decide(FaultOp::kRingComplete, EndPoint(), 0);
        if (fault.kind == FaultAction::kDelay) {
            usleep((useconds_t)fault.delay_us);
        } else if (fault.kind == FaultAction::kDrop) {
            return 0;  // claimed done, never completed
        }
    }
    RingSync* sync = (RingSync*)mu_;
    std::lock_guard<std::mutex> lk(sync->mu);
    const uint64_t head = head_.load(std::memory_order_relaxed);
    uint64_t tail = tail_.load(std::memory_order_relaxed);
    // `slot` must name an in-flight acquire: within [tail, head) and not
    // already marked done.
    bool inflight = false;
    for (uint64_t i = tail; i < head; ++i) {
        if ((uint32_t)(i % depth_) == slot) {
            inflight = !done_[slot];
            break;
        }
    }
    if (!inflight) return -1;
    done_[slot] = true;
    completed_.fetch_add(1, std::memory_order_relaxed);
    // Device tier attribution: one staged slot cycled through the ring
    // (ops only — the framed length inside the slot is the caller's).
    transport_stats::AddOp(TierDevice());
    // FIFO reuse: advance the reusable frontier only over a contiguous
    // prefix of completed slots (out-of-order completes wait here).
    while (tail < head && done_[tail % depth_]) {
        done_[tail % depth_] = false;
        ++tail;
    }
    tail_.store(tail, std::memory_order_relaxed);
    sync->cv.notify_all();
    return 0;
}

int IciBlockPool::Init(size_t region_bytes) {
    PoolState& p = pool();
    bool expected = false;
    if (!p.inited.compare_exchange_strong(expected, true)) return 0;
    {
        std::lock_guard<std::mutex> g(p.mu);
        p.region_step = region_bytes < (1u << 20) ? (1u << 20) : region_bytes;
        // The bounce reserve must fit INSIDE the primary (a reserve >=
        // the region would underflow carve_limit into an unbounded carve
        // — heap corruption): cap it at a quarter of the region.
        p.bounce_reserve =
            std::min(p.bounce_reserve, p.region_step / 4);
        // Primary region: shared (cross-process transferable). Fall back
        // to anonymous when /dev/shm is unavailable — in-process links
        // still work, cross-process connects will refuse.
        if (!create_shared_primary_locked(p) && !grow_locked(p)) {
            p.inited.store(false);
            return -1;
        }
    }
    // Publish our own pool under its descriptor id: in-process loopback
    // links (and any handler resolving a descriptor we posted to
    // ourselves) resolve against the same registry peers use.
    if (pool().shm_name[0] != '\0') {
        pool_registry::Register(pool_registry::IdFromName(pool().shm_name),
                                pool().shm_base, pool().shm_size,
                                pool_epoch());
        pool_registry::SetName(pool_registry::IdFromName(pool().shm_name),
                               pool().shm_name);
    }
    // Teach the Transport tier how to name this process's pool: the
    // descriptor-eligibility seam (tnet/transport.h) answers "may a
    // descriptor ride/resolve here" for every endpoint type without
    // tnet depending on the pool layer.
    SetLocalPoolIdProvider(&IciBlockPool::pool_id);
    // From here on every new IOBuf block is transferable memory (the
    // TLS block cache only recycles blocks whose deallocator matches the
    // current pair, so stale malloc'd blocks are not handed back out).
    // Deallocate hook FIRST: Init may run lazily (first ICI handshake on
    // a busy server) while other threads allocate; a racer that sees the
    // new allocator must also see a deallocator that can free its block
    // (Deallocate falls back to free() for non-pool pointers, so the
    // reverse mix is safe — free() on a pool block is not).
    IOBuf::blockmem_deallocate = &IciBlockPool::Deallocate;
    IOBuf::blockmem_allocate = &IciBlockPool::Allocate;
    IOBuf::blockmem_cache_veto = &shared_cache_veto;
    return 0;
}

bool IciBlockPool::initialized() {
    return pool().inited.load(std::memory_order_acquire);
}

size_t IciBlockPool::allocated_blocks() {
    return pool().live.load(std::memory_order_relaxed);
}

size_t IciBlockPool::free_blocks() {
    PoolState& p = pool();
    std::lock_guard<std::mutex> g(p.mu);
    return p.freelist_shared.size() + p.freelist_other.size();
}

}  // namespace tpurpc
