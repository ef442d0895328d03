// Registered-memory block pool for the ICI transport: takes over IOBuf's
// block allocator so every payload block lives in transfer-registered
// memory and can be posted to the interconnect zero-copy.
//
// Modeled on reference src/brpc/rdma/block_pool.{h,cpp} (628 LoC): the
// RDMA build registers GB-step regions with the NIC and swaps IOBuf's
// `blockmem_allocate` hook (butil/iobuf.cpp:168) so send buffers need no
// bounce copy. Here "registered" means: the PRIMARY region is a named
// POSIX shared-memory segment other processes can map (the cross-process
// "memory registration"), so a peer can resolve posted (offset,length)
// descriptors against its read-only mapping of this pool — on real
// TPU-VM hosts this seam becomes libtpu-registered / pinned host
// buffers. Overflow regions are anonymous (non-transferable; the send
// path bounce-copies from them). Structure kept from the reference:
// regions grown in fixed steps, freelist under a mutex (the per-thread
// IOBuf block cache in front absorbs nearly all traffic), O(1)
// Contains() via the region list.
#pragma once

#include <atomic>
#include <cstddef>
#include <cstdint>
#include <string>

namespace tpurpc {

class IciBlockPool {
public:
    // Install the pool as IOBuf's block allocator. Idempotent.
    // `region_bytes` sizes the primary (shared, transferable) region;
    // overflow grows in anonymous regions of the same step.
    static int Init(size_t region_bytes = 64u << 20);

    // Allocator pair installed into IOBuf::blockmem_allocate/deallocate.
    static void* Allocate(size_t n);
    static void Deallocate(void* p);
    // A DEFAULT_BLOCK_SIZE block guaranteed inside the shared region, or
    // null when none is free (bounce buffers for the cross-process send
    // path, which must be peer-visible). Deallocate() as usual.
    static void* AllocateSharedBlock();
    // `n` DEFAULT_BLOCK_SIZE blocks of the shared region's general area
    // into out[0..n), taken in one batch under the pool mutex: freed
    // shared blocks first, then the primary's carve. All or none: false,
    // and nothing taken, when the region cannot give n -- never from the
    // bounce band, never from an overflow region. The caller frees each
    // with DeallocateShared, so it comes back here and to no thread's
    // cache.
    static bool AllocateSharedBlocks(size_t n, void** out);

    // A large contiguous chunk carved from registered region memory —
    // staging buffers for device DMA (the JAX device-path benchmark
    // device_puts straight out of these). Carve-only: chunks live for the
    // process (free is a no-op); intended for long-lived transfer
    // arenas, like the reference's GB-step RDMA regions
    // (/root/reference/src/brpc/rdma/block_pool.cpp RegisterMemory).
    static void* AllocateRegistered(size_t n);
    // Deallocator for bounce blocks: same routing as Deallocate, but a
    // DISTINCT function pointer so IOBuf::Block::dec_ref bypasses the TLS
    // block cache (bounce blocks must return to the shared freelist where
    // AllocateSharedBlock can find them, not vanish into a thread cache).
    static void DeallocateShared(void* p);

    // True if p lies inside a registered region (pool memory; primary or
    // overflow).
    static bool Contains(const void* p);

    // ---- slab-class registered allocator (ISSUE 9c) ----
    // Recyclable registered memory in size classes (8K/64K/256K/1M/4M).
    // Each class carves large aligned slab ARENAS out of the registered
    // regions and chops them into fixed slots; freed slots recycle
    // through a per-thread slot cache in front of a per-class freelist
    // (its own mutex), so descriptor/staging traffic never bounces on
    // the pool's central mutex. Requests above the largest class fall
    // back to AllocateRegistered (carve-only, process lifetime).
    static void* AllocateSlab(size_t n);
    // Recycles p into its class (TLS cache first). p MUST come from
    // AllocateSlab; non-slab pool pointers are ignored (carve-only).
    static void FreeSlab(void* p);
    // Class index serving n bytes, or -1 when n exceeds the largest
    // class (tests + sizing diagnostics).
    static int SlabClassOf(size_t n);
    static size_t slab_class_bytes(int cls);
    // Counters: live slots, frees that found a cache/freelist home, and
    // class-mutex acquisitions (the contention diagnostic the per-thread
    // cache is meant to keep near zero on steady-state traffic).
    static size_t slab_allocated();
    static size_t slab_recycled();
    static size_t slab_mutex_acquisitions();
    // Per-class occupancy (the /pools page): live slots, freelist depth
    // (central list only — TLS-cached slots count as live-capable but
    // not listed), and slots carved so far.
    struct SlabClassStat {
        size_t live = 0;
        size_t freelist = 0;
        size_t carved = 0;
    };
    static SlabClassStat slab_class_stat(int cls);

    // Build a single-block IOBuf of n writable bytes inside the SHARED
    // registered pool — the eligible shape for one-sided descriptors
    // (Controller::set_request_pool_attachment): one contiguous ref a
    // single (offset, len) can name. The block wraps a slab slot
    // (placement-new IOBuf::Block header, FreeSlab deallocator), so the
    // last release recycles the slot into its class. Returns false when
    // n exceeds the largest slab class or the slab landed outside the
    // shared primary (caller falls back to inline attachment bytes).
    static bool AllocatePoolAttachment(size_t n, class IOBuf* out,
                                       char** data);

    // Chunk-leasing helper for pipelined transfers (ISSUE 13): allocate
    // a descriptor-eligible pool block and fill it from `src` in one
    // step — the shape every collective chunk send needs. Returns false
    // (out untouched) when the pool can't serve a shared slab of n
    // bytes; the caller falls back to inline attachment bytes.
    static bool AllocatePoolAttachmentCopy(const void* src, size_t n,
                                           class IOBuf* out);

    // ---- cross-process registration (the shared primary region) ----
    // Name of the shm segment backing the primary region ("" when the
    // pool fell back to anonymous memory). Peers shm_open this name
    // during the ICI handshake.
    static const char* shm_name();
    static size_t shm_size();
    static char* shm_base();
    // True + byte offset into the shared region when p points into it —
    // i.e. the bytes at p can be posted to a peer zero-copy.
    static bool OffsetOf(const void* p, uint64_t* offset);

    // Stable identity of this process's shared primary region (FNV-1a of
    // the shm name; 0 when the pool is anonymous/process-local). The
    // pool_id of one-sided descriptors posted from this pool.
    static uint64_t pool_id();

    // ---- epoch fencing (ISSUE 10b) ----
    // Generation of this process's pool mapping: 1 at Init, bumped on
    // any create/remap/restart event (and by chaos/tests). Descriptors
    // carry the epoch they were minted under (RpcMeta.pool_attachment.
    // pool_epoch); a receiver resolving against a mapping whose epoch
    // differs fails ONLY that call with the retriable TERR_STALE_EPOCH —
    // a stale reference must never take down the connection or the
    // process, just trigger a re-handshake/remap upstream.
    static uint64_t pool_epoch();
    // Bump the local pool's generation (simulated remap/restart; also
    // re-stamps the pool's own registry entry so in-process resolution
    // stays consistent). Returns the new epoch.
    static uint64_t BumpEpoch();

    static bool initialized();
    static size_t allocated_blocks();  // live default-size blocks
    static size_t free_blocks();       // freelist depth
};

// ---- pool registry (one-sided descriptors, ISSUE 9b) ----
// Maps pool_id -> a mapping of that pool in THIS process's address
// space: the local pool (registered at IciBlockPool::Init) and every
// peer pool mapped during an ICI handshake (shm_link AcquirePeerPool).
// A receiver resolves a wire (pool_id, offset, len) descriptor here and
// reads the bytes in place — the one-sided read of the transfer.
namespace pool_registry {
uint64_t IdFromName(const char* name);  // FNV-1a 64 over the shm name
// `epoch` is the pool generation this mapping was made under (learned
// from the owner at handshake; the local pool registers its own).
void Register(uint64_t id, const char* base, size_t size,
              uint64_t epoch = 1);
void Unregister(uint64_t id);
// Re-stamp a mapping's generation without remapping (the local pool's
// BumpEpoch, and chaos-driven staleness in tests). Absolute write —
// test hook; production paths use RaiseEpoch.
void SetEpoch(uint64_t id, uint64_t epoch);
// Monotonic re-stamp: only raises the mapping's generation. The
// handshake path uses this — a slow/racing link whose response was
// written before the owner's bump must not REGRESS the epoch (stale
// descriptors would then pass the fence again).
void RaiseEpoch(uint64_t id, uint64_t epoch);
// True + the mapped span when id is known. The span stays valid while
// the mapping is held (local pool: process lifetime; peer pools: while
// any link to that peer lives — the Socket holding the descriptor's
// connection holds the link, so resolution during request processing is
// safe). `epoch` (when non-null) receives the mapping's generation for
// the caller's stale-descriptor fence.
bool Resolve(uint64_t id, const char** base, size_t* size,
             uint64_t* epoch = nullptr);
// Pool id -> shm segment name (ISSUE 18). The verbs layer needs the
// NAME to open its own WRITABLE mapping of a peer pool (the handshake
// mapping is PROT_READ; a granted REMOTE_WRITE window is the rkey-
// equivalent authorization to remap O_RDWR). Registered alongside the
// mapping; survives Unregister so a re-grant after link churn can
// still find the segment. NameOf copies into buf (NUL-terminated),
// false when unknown or buf too small.
void SetName(uint64_t id, const char* name);
bool NameOf(uint64_t id, char* buf, size_t n);
// Resolution stats (tests + /vars).
uint64_t resolves();
uint64_t resolve_failures();
// One "pool <id> size=<n> epoch=<e> local=<0|1>" line per mapping (the
// /pools page body).
std::string DebugString();
}  // namespace pool_registry

// ---- device staging ring (ISSUE 9a) ----
// A depth-N ring of registered staging slots driving the pipelined
// device data path: slot i holds chunk i's framed bytes while H2D of
// chunk i+1, the on-device integrity kernel on chunk i, and D2H of
// chunk i-1 overlap. Slots are handed out in strict FIFO order
// (Acquire blocks while the oldest slot is still in flight) and become
// reusable only when every predecessor has completed — the same
// released_-counter protocol as the shm/ici descriptor rings, which is
// what makes out-of-order Complete() calls safe under many threads.
//
// Thread contract: plain std::mutex/condvar (NOT fibers) — the ring is
// driven from Python threads through the C ABI.
class DeviceStagingRing {
public:
    // Slots come from AllocateSlab: registered memory, recycled on
    // destroy. Returns null when depth/slot_bytes is zero or the pool
    // has no memory.
    static DeviceStagingRing* Create(uint32_t depth, size_t slot_bytes);
    ~DeviceStagingRing();

    // Next slot in FIFO order; blocks up to timeout_us (<0 = forever,
    // 0 = non-blocking try) while all depth slots are in flight.
    // Returns the slot index, -1 on timeout, or -2 once the ring is
    // aborted (waiters unblock immediately — the deadline/cancellation
    // contract of ISSUE 10c).
    int Acquire(int64_t timeout_us);
    // Mark slot done. Out-of-order completes are held; the slot is
    // reusable once all earlier acquires completed. Returns 0, or -1
    // for an index that is not currently in flight. Chaos may delay or
    // drop a complete (chaos_pool ring_delay/ring_drop): a dropped
    // complete returns 0 but never advances the window — exactly the
    // lost-completion failure Acquire's timeout path must survive.
    int Complete(uint32_t slot);
    // Poison the ring (device stream error / shutdown): every parked and
    // future Acquire returns -2 instead of wedging a Python thread
    // forever; in-flight Completes still settle accounting.
    void Abort();
    bool aborted() const {
        return aborted_.load(std::memory_order_acquire);
    }

    char* slot(uint32_t i) { return slots_[i % depth_]; }
    uint32_t depth() const { return depth_; }
    size_t slot_bytes() const { return slot_bytes_; }
    bool registered() const { return registered_; }
    uint64_t acquires() const {
        return head_.load(std::memory_order_relaxed);
    }
    uint64_t completes() const {
        return completed_.load(std::memory_order_relaxed);
    }
    // Highest number of slots ever simultaneously in flight (ordering
    // tests: never exceeds depth).
    uint32_t inflight_highwater() const {
        return highwater_.load(std::memory_order_relaxed);
    }

private:
    DeviceStagingRing() = default;

    void* mu_ = nullptr;  // std::mutex + condvar behind an opaque ptr
    char** slots_ = nullptr;
    // How each slot was obtained (0 = slab class / recyclable, 1 =
    // malloc fallback, 2 = carve-only registered chunk): ~Ring must
    // route each pointer back to the right deallocator.
    uint8_t* slot_kind_ = nullptr;
    bool* done_ = nullptr;
    uint32_t depth_ = 0;
    size_t slot_bytes_ = 0;
    bool registered_ = false;
    // Counters mutate under mu_ but are read lock-free by the accessors.
    std::atomic<uint64_t> head_{0};       // acquired count
    std::atomic<uint64_t> tail_{0};       // contiguously-completed count
    std::atomic<uint64_t> completed_{0};  // total completes
    std::atomic<uint32_t> highwater_{0};
    std::atomic<bool> aborted_{false};    // poisoned: Acquire returns -2
};

}  // namespace tpurpc
