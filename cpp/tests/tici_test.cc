// ICI transport tests: the fake-ICI loopback link plays the role loopback
// TCP plays in the reference's tests (SURVEY §4: "a fake/loopback ICI
// endpoint plays the role loopback TCP plays"). Covers the block pool,
// the queue-pair data path, credit flow control, event suppression, EOF,
// and a full RPC echo over the link.
#include <sys/wait.h>
#include <unistd.h>

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstring>
#include <mutex>
#include <string>
#include <thread>
#include <vector>

#include "echo.pb.h"
#include "rpc_meta.pb.h"
#include "tbase/crc32c.h"
#include "tbase/iobuf.h"
#include "tbase/errno.h"
#include "tbase/fast_rand.h"
#include "tbase/flags.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "tfiber/fiber_sync.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tici/ici_link.h"
#include "tnet/socket.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "trpc/pb_compat.h"
#include "trpc/policy_tpu_std.h"
#include "trpc/server.h"
#include "ttest/ttest.h"

using namespace tpurpc;

namespace {

// Pump endpoint `e` into `portal` until `want` bytes arrived (poll-style,
// for link-level tests that bypass the dispatcher).
ssize_t pump_until(IciEndpoint* e, IOPortal* portal, size_t want) {
    ssize_t total = 0;
    for (int spins = 0; spins < 100000 && (size_t)total < want; ++spins) {
        const ssize_t nr = e->Pump(portal);
        if (nr > 0) {
            total += nr;
        } else if (nr == 0) {
            return total;  // EOF
        }
    }
    return total;
}

}  // namespace

TEST(IciBlockPool, InstallsAndServesRegisteredMemory) {
    ASSERT_EQ(0, IciBlockPool::Init());
    ASSERT_TRUE(IciBlockPool::initialized());
    // New IOBuf blocks now come from registered regions.
    IOBuf buf;
    buf.append(std::string(100, 'x'));
    size_t len = 0;
    const char* p = buf.backing_block_data(0, &len);
    EXPECT_TRUE(IciBlockPool::Contains(p));
    EXPECT_EQ(100u, len);
    // Odd-size direct allocation round-trips too.
    void* odd = IciBlockPool::Allocate(123456);
    ASSERT_TRUE(odd != nullptr);
    IciBlockPool::Deallocate(odd);
}

TEST(IciLink, BytesFlowBothWays) {
    IciLink& link = *IciLink::Create();
    IOBuf msg;
    msg.append("hello over ici");
    IOBuf* pieces[1] = {&msg};
    ASSERT_EQ((ssize_t)14, link.first()->CutFromIOBufList(pieces, 1));
    EXPECT_TRUE(msg.empty());

    IOPortal in;
    ASSERT_EQ((ssize_t)14, pump_until(link.second(), &in, 14));
    EXPECT_TRUE(in.equals("hello over ici"));

    // Reverse direction.
    IOBuf rev;
    rev.append("pong");
    IOBuf* rp[1] = {&rev};
    ASSERT_EQ((ssize_t)4, link.second()->CutFromIOBufList(rp, 1));
    IOPortal rin;
    ASSERT_EQ((ssize_t)4, pump_until(link.first(), &rin, 4));
    EXPECT_TRUE(rin.equals("pong"));
    link.first()->Release();
    link.second()->Release();
}

TEST(IciLink, LargeTransferSurvivesWindowRecycling) {
    // 8MB >> the 256-descriptor window: requires credits to recycle.
    IciLink& link = *IciLink::Create();
    const size_t kTotal = 8u << 20;
    std::string big(kTotal, 0);
    for (size_t i = 0; i < kTotal; ++i) big[i] = (char)(i * 1315423911u >> 7);
    IOBuf src;
    src.append(big);

    std::atomic<bool> done{false};
    std::string got;
    got.reserve(kTotal);
    // Consumer fiber: pump into a portal, drain to string.
    struct Ctx {
        IciLink* link;
        std::string* got;
        size_t want;
        std::atomic<bool>* done;
    } ctx{&link, &got, kTotal, &done};
    fiber_t consumer;
    fiber_start_background(
        &consumer, nullptr,
        [](void* a) -> void* {
            Ctx* c = (Ctx*)a;
            IOPortal in;
            while (c->got->size() < c->want) {
                const ssize_t nr = c->link->second()->Pump(&in);
                if (nr > 0) {
                    std::string chunk;
                    in.cutn(&chunk, in.size());
                    c->got->append(chunk);
                } else if (nr == 0) {
                    break;
                } else {
                    fiber_usleep(100);
                }
            }
            c->done->store(true);
            return nullptr;
        },
        &ctx);

    // Producer: post with window waits.
    IOBuf* pieces[1] = {&src};
    while (!src.empty()) {
        const ssize_t nw = link.first()->CutFromIOBufList(pieces, 1);
        if (nw < 0 && errno == EAGAIN) {
            ASSERT_EQ(0, link.first()->WaitWritable(monotonic_time_us() +
                                                    2 * 1000 * 1000));
        } else {
            ASSERT_GT(nw, 0);
        }
    }
    fiber_join(consumer, nullptr);
    ASSERT_TRUE(done.load());
    ASSERT_EQ(kTotal, got.size());
    EXPECT_EQ(0, memcmp(got.data(), big.data(), kTotal));
    link.first()->Release();
    link.second()->Release();
}

TEST(IciLink, EventSuppressionBatchesDoorbells) {
    IciLink& link = *IciLink::Create();
    // Burst of 50 posts with no consumer arm/drain in between: the
    // doorbell fires once for the burst, not 50 times.
    for (int i = 0; i < 50; ++i) {
        IOBuf m;
        m.append("x");
        IOBuf* p[1] = {&m};
        ASSERT_EQ((ssize_t)1, link.first()->CutFromIOBufList(p, 1));
    }
    EXPECT_EQ(1u, link.first()->signals_sent());
    IOPortal in;
    EXPECT_EQ((ssize_t)50, pump_until(link.second(), &in, 50));
    link.first()->Release();
    link.second()->Release();
}

TEST(IciLink, CloseDeliversEofAfterDrain) {
    IciLink& link = *IciLink::Create();
    IOBuf m;
    m.append("last words");
    IOBuf* p[1] = {&m};
    ASSERT_EQ((ssize_t)10, link.first()->CutFromIOBufList(p, 1));
    link.first()->Close();
    IOPortal in;
    // Data still delivered...
    ASSERT_EQ((ssize_t)10, pump_until(link.second(), &in, 10));
    EXPECT_TRUE(in.equals("last words"));
    // ...then EOF.
    EXPECT_EQ((ssize_t)0, link.second()->Pump(&in));
    // Writes now fail.
    IOBuf m2;
    m2.append("x");
    IOBuf* p2[1] = {&m2};
    EXPECT_EQ((ssize_t)-1, link.second()->CutFromIOBufList(p2, 1));
    link.first()->Release();
    link.second()->Release();
}

// ---------------- slab-class allocator (ISSUE 9c) ----------------

TEST(SlabPool, ClassesGrowAndRecycle) {
    ASSERT_EQ(0, IciBlockPool::Init());
    // Size -> class mapping across the ladder.
    EXPECT_EQ(0, IciBlockPool::SlabClassOf(1));
    EXPECT_EQ(0, IciBlockPool::SlabClassOf(8u << 10));
    EXPECT_EQ(1, IciBlockPool::SlabClassOf((8u << 10) + 1));
    EXPECT_EQ(2, IciBlockPool::SlabClassOf(100u << 10));
    EXPECT_EQ(3, IciBlockPool::SlabClassOf(1u << 20));
    EXPECT_EQ(4, IciBlockPool::SlabClassOf(4u << 20));
    EXPECT_EQ(-1, IciBlockPool::SlabClassOf((4u << 20) + 1));

    // Grow: a fresh slot, registered memory, live count up.
    const size_t live0 = IciBlockPool::slab_allocated();
    void* a = IciBlockPool::AllocateSlab(5000);
    ASSERT_TRUE(a != nullptr);
    EXPECT_TRUE(IciBlockPool::Contains(a));
    EXPECT_EQ(live0 + 1, IciBlockPool::slab_allocated());

    // Recycle: free then realloc the same class returns the cached slot
    // (TLS cache is LIFO) and bumps the recycle counter.
    const size_t rec0 = IciBlockPool::slab_recycled();
    IciBlockPool::FreeSlab(a);
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());
    void* b = IciBlockPool::AllocateSlab(6000);
    EXPECT_EQ(a, b);
    EXPECT_EQ(rec0 + 1, IciBlockPool::slab_recycled());
    IciBlockPool::FreeSlab(b);

    // Distinct classes never alias each other's slots.
    void* small = IciBlockPool::AllocateSlab(100);
    void* big = IciBlockPool::AllocateSlab(60u << 10);
    EXPECT_TRUE(small != big);
    IciBlockPool::FreeSlab(small);
    IciBlockPool::FreeSlab(big);

    // Oversized requests fall back to carve-only registered chunks:
    // non-null, registered, and FreeSlab is a safe no-op on them.
    void* huge = IciBlockPool::AllocateSlab(5u << 20);
    ASSERT_TRUE(huge != nullptr);
    EXPECT_TRUE(IciBlockPool::Contains(huge));
    IciBlockPool::FreeSlab(huge);
}

TEST(SlabPool, PerThreadCacheKeepsClassMutexCold) {
    ASSERT_EQ(0, IciBlockPool::Init());
    // Prime every thread's cache, then hammer alloc/free: steady-state
    // traffic must run out of the TLS cache, not the class mutex.
    constexpr int kThreads = 8;
    constexpr int kOps = 2000;
    const size_t mu0 = IciBlockPool::slab_mutex_acquisitions();
    const size_t rec0 = IciBlockPool::slab_recycled();
    std::vector<std::thread> threads;
    for (int t = 0; t < kThreads; ++t) {
        threads.emplace_back([] {
            for (int i = 0; i < kOps; ++i) {
                void* p = IciBlockPool::AllocateSlab(4096);
                ASSERT_TRUE(p != nullptr);
                memset(p, 0xAB, 64);
                IciBlockPool::FreeSlab(p);
            }
        });
    }
    for (auto& th : threads) th.join();
    const size_t mutex_touches =
        IciBlockPool::slab_mutex_acquisitions() - mu0;
    const size_t recycled = IciBlockPool::slab_recycled() - rec0;
    // kThreads*kOps operations; all but the cold-start allocations (and
    // the thread-exit cache drains) must recycle without the mutex.
    EXPECT_GE(recycled, (size_t)(kThreads * kOps - kThreads * 2));
    EXPECT_LE(mutex_touches, (size_t)(kThreads * 4));
}

// Regression for the descriptor leg on a many-core host: 1MiB pool
// attachments are allocated by the caller and released wherever the
// last reference drops — fiber workers, hardware_concurrency()+1 of
// them. Parked in those threads' caches (8 slots each) the slots never
// came back to the allocating thread; every round carved a fresh one,
// the 64MiB shm region ran out, the next arena landed in anonymous
// overflow and OffsetOf refused it ("pool attachment alloc failed").
TEST(SlabPool, JumboSlotsFreedOnOtherThreadsComeBack) {
    ASSERT_EQ(0, IciBlockPool::Init());
    const int kWorkers = (int)std::thread::hardware_concurrency() + 1;
    const int kRounds = 600;
    const size_t kBytes = (1u << 20) - 128;  // echo_bench's descriptor size
    const int cls = IciBlockPool::SlabClassOf(1u << 20);
    const size_t carved0 = IciBlockPool::slab_class_stat(cls).carved;

    // Long-lived workers (a thread's cache drains when it exits, which
    // would hide the stranding), one mailbox each.
    struct Mailbox {
        std::mutex mu;
        std::condition_variable cv;
        IOBuf* item = nullptr;
        bool quit = false;
    };
    std::vector<Mailbox> boxes((size_t)kWorkers);
    std::vector<std::thread> workers;
    for (int w = 0; w < kWorkers; ++w) {
        workers.emplace_back([&boxes, w] {
            Mailbox& b = boxes[(size_t)w];
            std::unique_lock<std::mutex> lk(b.mu);
            while (true) {
                b.cv.wait(lk, [&b] { return b.item != nullptr || b.quit; });
                if (b.item == nullptr) return;
                delete b.item;  // last reference: the slot is freed HERE
                b.item = nullptr;
                b.cv.notify_all();
            }
        });
    }
    int failed_round = -1;
    for (int r = 0; r < kRounds && failed_round < 0; ++r) {
        auto* att = new IOBuf;
        char* data = nullptr;
        if (!IciBlockPool::AllocatePoolAttachment(kBytes, att, &data)) {
            delete att;
            failed_round = r;
            break;
        }
        data[0] = (char)r;
        Mailbox& b = boxes[(size_t)(r % kWorkers)];
        std::unique_lock<std::mutex> lk(b.mu);
        b.item = att;
        b.cv.notify_all();
        b.cv.wait(lk, [&b] { return b.item == nullptr; });
    }
    for (Mailbox& b : boxes) {
        std::lock_guard<std::mutex> lk(b.mu);
        b.quit = true;
        b.cv.notify_all();
    }
    for (auto& th : workers) th.join();
    EXPECT_EQ(-1, failed_round);  // never left the shm region
    // One attachment live at a time: at most one fresh carve, whatever
    // the host's core count.
    EXPECT_LE(IciBlockPool::slab_class_stat(cls).carved, carved0 + 1);
}

// ---------------- the region's general blocks, in batches ----------------

namespace {

constexpr size_t kBlock = IOBuf::DEFAULT_BLOCK_SIZE;
constexpr size_t kPartBlocks = 386;  // a 3 MiB reply part of 8160-byte payloads

// Runs `check` in a forked child and gives its exit status: what a check
// takes of the pool's books stays in the child's copy of them, so one that
// exhausts the region's carve leaves later tests their room. The region's
// memory is shared with the child; the checks take and give back blocks
// and write nothing into them. A check returns 0, or the number of the
// first expectation that failed; a child that hangs is ended by its alarm.
int InChild(int (*check)()) {
    const pid_t pid = fork();
    if (pid == 0) {
        alarm(30);
        _exit(check());
    }
    int status = 0;
    if (waitpid(pid, &status, 0) != pid) return -1;
    return WIFEXITED(status) ? WEXITSTATUS(status) : 128 + WTERMSIG(status);
}

// Where a block taken by AllocateSharedBlocks may lie: inside the region,
// whole, short of the link's bounce band (the region's last 8 MiB).
bool InGeneralArea(const void* b) {
    uint64_t off = 0;
    return IciBlockPool::OffsetOf(b, &off) && off % kBlock == 0 &&
           off + kBlock <= IciBlockPool::shm_size() - (8u << 20);
}

// Takes every block the region's general area can give, a part's worth at
// a time and then one at a time, and checks where each lies and what is
// left for the link.
int TakeTheWholeGeneralArea() {
    std::vector<void*> taken;
    std::vector<void*> part(kPartBlocks);
    while (IciBlockPool::AllocateSharedBlocks(kPartBlocks, part.data())) {
        taken.insert(taken.end(), part.begin(), part.end());
    }
    void* one = nullptr;
    while (IciBlockPool::AllocateSharedBlocks(1, &one)) taken.push_back(one);
    if (taken.size() < kPartBlocks) return 1;  // not even one part's worth
    for (void* b : taken) {
        if (!InGeneralArea(b)) return 2;
    }
    std::vector<void*> sorted = taken;
    std::sort(sorted.begin(), sorted.end());
    if (std::adjacent_find(sorted.begin(), sorted.end()) != sorted.end()) {
        return 3;  // a block given twice
    }
    // Dry: all or none, so a request it cannot serve takes nothing, and it
    // never grows the pool into an anonymous region for it.
    const size_t free0 = IciBlockPool::free_blocks();
    if (IciBlockPool::AllocateSharedBlocks(kPartBlocks, part.data())) return 4;
    if (IciBlockPool::free_blocks() != free0) return 5;
    // The link's bounce band is still its own.
    void* bounce = IciBlockPool::AllocateSharedBlock();
    uint64_t off = 0;
    if (bounce == nullptr || !IciBlockPool::OffsetOf(bounce, &off) ||
        off < IciBlockPool::shm_size() - (8u << 20)) {
        return 6;
    }
    IciBlockPool::DeallocateShared(bounce);
    // Given back, every block is on the shared freelist again: the whole
    // lot can be taken in one batch.
    for (void* b : taken) IciBlockPool::DeallocateShared(b);
    std::vector<void*> again(taken.size());
    if (!IciBlockPool::AllocateSharedBlocks(again.size(), again.data())) {
        return 7;
    }
    for (void* b : again) IciBlockPool::DeallocateShared(b);
    return 0;
}

// A Get's three parts taken and given back, round after round: the free
// count is back where it was after each.
int AGetsPartsComeBackEachRound() {
    std::vector<void*> blocks(3 * kPartBlocks);
    if (!IciBlockPool::AllocateSharedBlocks(blocks.size(), blocks.data())) {
        return 1;
    }
    for (void* b : blocks) IciBlockPool::DeallocateShared(b);
    const size_t free0 = IciBlockPool::free_blocks();
    for (int round = 0; round < 16; ++round) {
        for (size_t k = 0; k < 3; ++k) {
            if (!IciBlockPool::AllocateSharedBlocks(
                    kPartBlocks, blocks.data() + k * kPartBlocks)) {
                return 2;
            }
        }
        for (void* b : blocks) {
            if (!InGeneralArea(b)) return 3;
        }
        if (IciBlockPool::free_blocks() != free0 - blocks.size()) return 4;
        for (void* b : blocks) IciBlockPool::DeallocateShared(b);
        if (IciBlockPool::free_blocks() != free0) return 5;
    }
    return 0;
}

}  // namespace

TEST(SharedBlocks, TakeTheGeneralAreaOnlyAndNeverGrowForIt) {
    ASSERT_EQ(0, IciBlockPool::Init());
    ASSERT_TRUE(IciBlockPool::shm_base() != nullptr);
    EXPECT_EQ(0, InChild(TakeTheWholeGeneralArea));
}

TEST(SharedBlocks, AGetsPartsGoBackToTheSharedFreelist) {
    ASSERT_EQ(0, IciBlockPool::Init());
    ASSERT_TRUE(IciBlockPool::shm_base() != nullptr);
    EXPECT_EQ(0, InChild(AGetsPartsComeBackEachRound));
}

// ---------------- device staging ring (ISSUE 9a) ----------------

TEST(DeviceStagingRing, FifoAcquireCompleteOrderingUnder8Threads) {
    ASSERT_EQ(0, IciBlockPool::Init());
    DeviceStagingRing* ring = DeviceStagingRing::Create(4, 60u << 10);
    ASSERT_TRUE(ring != nullptr);
    EXPECT_EQ(4u, ring->depth());
    constexpr int kThreads = 8;
    constexpr int kPerThread = 200;
    std::atomic<int> inflight{0};
    std::atomic<int> max_inflight{0};
    std::atomic<int> failures{0};
    std::vector<std::thread> threads;
    for (int t = 0; t < kThreads; ++t) {
        threads.emplace_back([&, t] {
            for (int i = 0; i < kPerThread; ++i) {
                const int slot = ring->Acquire(5 * 1000 * 1000);
                if (slot < 0) {
                    failures.fetch_add(1);
                    return;
                }
                const int now = inflight.fetch_add(1) + 1;
                int prev = max_inflight.load();
                while (now > prev &&
                       !max_inflight.compare_exchange_weak(prev, now)) {
                }
                // Touch the slot, with jitter so completes go out of
                // acquire order routinely.
                memset(ring->slot((uint32_t)slot), t, 256);
                if (fast_rand() % 4 == 0) usleep(fast_rand() % 300);
                inflight.fetch_sub(1);
                if (ring->Complete((uint32_t)slot) != 0) {
                    failures.fetch_add(1);
                    return;
                }
            }
        });
    }
    for (auto& th : threads) th.join();
    EXPECT_EQ(0, failures.load());
    // Window never exceeded depth, and every acquire completed.
    EXPECT_LE(max_inflight.load(), 4);
    EXPECT_EQ((uint64_t)(kThreads * kPerThread), ring->acquires());
    EXPECT_EQ((uint64_t)(kThreads * kPerThread), ring->completes());
    EXPECT_LE(ring->inflight_highwater(), 4u);
    // Double-complete of an idle slot is rejected.
    EXPECT_EQ(-1, ring->Complete(0));
    delete ring;
}

// ---------------- one-sided pool descriptors (ISSUE 9b) ----------------

TEST(PoolDescriptor, MetaFrameParseRoundTrip) {
    ASSERT_EQ(0, IciBlockPool::Init());
    ASSERT_NE(0ull, IciBlockPool::pool_id());
    // Stage descriptor-eligible bytes in the shared pool.
    IOBuf att;
    char* data = nullptr;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(50000, &att, &data));
    memset(data, 'd', 50000);
    uint64_t off = 0;
    ASSERT_TRUE(IciBlockPool::OffsetOf(data, &off));
    const uint32_t crc = crc32c_extend(0, data, 50000);

    // Frame a descriptor-carrying meta (header + meta ONLY — no
    // attachment bytes in the body)...
    rpc::RpcMeta meta;
    meta.set_correlation_id(77);
    auto* pd = meta.mutable_pool_attachment();
    pd->set_pool_id(IciBlockPool::pool_id());
    pd->set_offset(off);
    pd->set_length(50000);
    pd->set_crc32c(crc);
    IOBuf meta_buf;
    ASSERT_TRUE(SerializePbToIOBuf(meta, &meta_buf));
    IOBuf frame;
    PackTpuStdFrame(&frame, meta_buf, IOBuf(), IOBuf());
    EXPECT_LT(frame.size(), (size_t)256);  // tiny wire frame for 50KB

    // ...parse it back and resolve the descriptor against the registry.
    ParseResult r = ParseTpuStdMessage(&frame, nullptr, false, nullptr);
    ASSERT_TRUE(r.error == ParseError::OK);
    std::unique_ptr<TpuStdMessage> msg((TpuStdMessage*)r.msg);
    rpc::RpcMeta parsed;
    ASSERT_TRUE(ParsePbFromIOBuf(&parsed, msg->meta));
    ASSERT_TRUE(parsed.has_pool_attachment());
    EXPECT_EQ(IciBlockPool::pool_id(), parsed.pool_attachment().pool_id());
    EXPECT_EQ(off, parsed.pool_attachment().offset());
    EXPECT_EQ(50000ull, parsed.pool_attachment().length());
    const char* base = nullptr;
    size_t psize = 0;
    ASSERT_TRUE(pool_registry::Resolve(parsed.pool_attachment().pool_id(),
                                       &base, &psize));
    ASSERT_LE(parsed.pool_attachment().offset() +
                  parsed.pool_attachment().length(),
              psize);
    // The resolved view IS the staged memory (zero-copy), and its bytes
    // hash to the descriptor's crc.
    EXPECT_EQ((const void*)data,
              (const void*)(base + parsed.pool_attachment().offset()));
    EXPECT_EQ(crc, crc32c_extend(0, base + parsed.pool_attachment().offset(),
                                 parsed.pool_attachment().length()));
}

namespace {

// Echo service reading the one-sided attachment IN PLACE: proves the
// view points into this process's registered pool and that no inline
// copy of the bytes arrived, then answers with the crc it computed.
class PoolDescEchoService : public test::EchoService {
public:
    void Echo(google::protobuf::RpcController* cntl_base,
              const test::EchoRequest* req, test::EchoResponse* res,
              google::protobuf::Closure* done) override {
        Controller* cntl = static_cast<Controller*>(cntl_base);
        const Controller::PoolAttachment& pa =
            cntl->request_pool_attachment();
        last_view_in_pool.store(pa.data != nullptr &&
                                IciBlockPool::Contains(pa.data));
        last_inline_bytes.store(
            (int64_t)cntl->request_attachment().size());
        if (pa.data != nullptr) {
            res->set_message(std::to_string(
                crc32c_extend(0, pa.data, pa.length)));
        } else {
            res->set_message("no descriptor");
        }
        done->Run();
    }
    std::atomic<bool> last_view_in_pool{false};
    std::atomic<int64_t> last_inline_bytes{-1};
};

}  // namespace

TEST(PoolDescriptor, RpcZeroCopyOverIciLink) {
    ASSERT_EQ(0, IciBlockPool::Init());
    PoolDescEchoService service;
    Server server;
    ASSERT_EQ(0, server.AddService(&service));
    ASSERT_EQ(0, server.StartNoListen(nullptr));

    IciLink& link = *IciLink::Create();
    SocketOptions sopts;
    sopts.fd = link.second()->event_fd();
    sopts.transport = link.second();
    sopts.owns_transport = true;
    sopts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    sopts.user = server.messenger();
    SocketId server_sid;
    ASSERT_EQ(0, Socket::Create(sopts, &server_sid));
    SocketOptions copts;
    copts.fd = link.first()->event_fd();
    copts.transport = link.first();
    copts.owns_transport = true;
    copts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    copts.user = Channel::client_messenger();
    SocketId client_sid;
    ASSERT_EQ(0, Socket::Create(copts, &client_sid));
    Channel channel;
    ChannelOptions chopts;
    chopts.timeout_ms = 5000;
    ASSERT_EQ(0, channel.InitWithSocketId(client_sid, &chopts));
    test::EchoService_Stub stub(&channel);

    const size_t kBytes = 60000;
    const size_t live0 = IciBlockPool::slab_allocated();
    IOBuf att;
    char* data = nullptr;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(kBytes, &att, &data));
    for (size_t i = 0; i < kBytes; ++i) data[i] = (char)(i * 31 >> 3);
    const uint32_t crc = crc32c_extend(0, data, kBytes);

    Controller cntl;
    cntl.set_request_pool_attachment(std::move(att));
    ASSERT_TRUE(cntl.has_request_pool_attachment());
    test::EchoRequest req;
    test::EchoResponse res;
    req.set_message("desc");
    stub.Echo(&cntl, &req, &res, nullptr);
    ASSERT_FALSE(cntl.Failed());
    // The server computed the crc from the IN-PLACE view (inside this
    // process's registered pool — loopback link, one address space) and
    // saw ZERO inline attachment bytes: the payload was never
    // duplicated host-side.
    EXPECT_EQ(std::to_string(crc), res.message());
    EXPECT_TRUE(service.last_view_in_pool.load());
    EXPECT_EQ((int64_t)0, service.last_inline_bytes.load());
    // Completion returned the pinned block to the owner's pool: the
    // slab live count is back at its baseline (EndRPC ran before the
    // sync stub returned).
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    SocketUniquePtr cs;
    ASSERT_EQ(0, Socket::AddressSocket(client_sid, &cs));
    cs->SetFailedWithError(TERR_CLOSE);
    cs.reset();
    server.Stop();
    server.Join();
}

// ---------------- block leases + epoch fencing (ISSUE 10) ----------------

TEST(BlockLease, ExactlyOnceReleaseAndExpiryReap) {
    ASSERT_EQ(0, IciBlockPool::Init());
    const size_t live0 = IciBlockPool::slab_allocated();

    // Pin -> exactly-once release: the second Release is a counted
    // no-op, never a double free (the EndRPC/retry/backup guarantee).
    IOBuf att;
    char* data = nullptr;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(10000, &att, &data));
    const uint64_t pinned0 = block_lease::pinned();
    const uint64_t lease = block_lease::Pin(std::move(att));
    ASSERT_NE(0ull, lease);
    EXPECT_TRUE(block_lease::Alive(lease));
    EXPECT_EQ(pinned0 + 1, block_lease::pinned());
    EXPECT_EQ(live0 + 1, IciBlockPool::slab_allocated());
    EXPECT_TRUE(block_lease::Release(lease));
    EXPECT_FALSE(block_lease::Release(lease));  // exactly once
    EXPECT_FALSE(block_lease::Alive(lease));
    EXPECT_EQ(pinned0, block_lease::pinned());
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    // Expiry reap: an armed lease whose deadline passed is reclaimed by
    // the reaper; a later (late) Release finds nothing.
    IOBuf att2;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(10000, &att2, &data));
    const uint64_t l2 = block_lease::Pin(std::move(att2));
    block_lease::Arm(l2, /*call_id=*/42,
                     monotonic_time_us() - 10 * 1000 * 1000,
                     block_lease::kNoPeer);
    const uint64_t reaped0 = block_lease::expired_reaped();
    EXPECT_GE(block_lease::ReapExpired(monotonic_time_us()), (size_t)1);
    EXPECT_EQ(reaped0 + 1, block_lease::expired_reaped());
    EXPECT_FALSE(block_lease::Alive(l2));
    EXPECT_FALSE(block_lease::Release(l2));  // reaper got there first
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    // A fresh (never-Armed) pin carries the DEFAULT lifetime from the
    // moment of the pin — alive now, reapable once -pool_lease_default_ms
    // passes: there is no unreapable pin state, even when the owner
    // dies before Arm.
    IOBuf att3;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(10000, &att3, &data));
    const uint64_t l3 = block_lease::Pin(std::move(att3));
    EXPECT_EQ((size_t)0, block_lease::ReapExpired(monotonic_time_us()));
    EXPECT_TRUE(block_lease::Alive(l3));
    EXPECT_GE(block_lease::ReapExpired(monotonic_time_us() +
                                       (int64_t)3600e6),
              (size_t)1);  // way past the default window
    EXPECT_FALSE(block_lease::Alive(l3));
    EXPECT_FALSE(block_lease::Release(l3));
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());
}

TEST(BlockLease, BackupTryHoldsBothPeersEntitled) {
    ASSERT_EQ(0, IciBlockPool::Init());
    const size_t live0 = IciBlockPool::slab_allocated();
    char* data = nullptr;
    IOBuf att;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &att, &data));
    const uint64_t l = block_lease::Pin(std::move(att));
    const int64_t dl = monotonic_time_us() + (int64_t)60e6;
    // Try 1 posts on socket 111; the backup try ADDS socket 222.
    ASSERT_TRUE(block_lease::Arm(l, 1, dl, 111, /*add_peer=*/false));
    ASSERT_TRUE(block_lease::Arm(l, 1, dl, 222, /*add_peer=*/true));
    // The backup's peer dies: the ORIGINAL try's server may still be
    // reading the block — the pin must survive.
    EXPECT_EQ((size_t)0, block_lease::ReleasePeer(222));
    EXPECT_TRUE(block_lease::Alive(l));
    // Once the last entitled peer is gone, the pin frees.
    EXPECT_EQ((size_t)1, block_lease::ReleasePeer(111));
    EXPECT_FALSE(block_lease::Alive(l));
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    // A RETRY (add_peer=false) replaces the key: the old socket's death
    // then frees nothing.
    IOBuf att2;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &att2, &data));
    const uint64_t l2 = block_lease::Pin(std::move(att2));
    ASSERT_TRUE(block_lease::Arm(l2, 2, dl, 111, false));
    ASSERT_TRUE(block_lease::Arm(l2, 2, dl, 333, false));
    EXPECT_EQ((size_t)0, block_lease::ReleasePeer(111));
    EXPECT_TRUE(block_lease::Alive(l2));
    EXPECT_EQ((size_t)1, block_lease::ReleasePeer(333));
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());
}

TEST(BlockLease, LateLoserAckValidatesCallAndPeer) {
    // ISSUE 16 regression: a hedged call posts the SAME pinned request
    // block to TWO peers; the winner's ack releases the lease, and the
    // LOSING try's response can land AFTER that, on a DIFFERENT
    // connection. Its drop-path ack must validate (call, peer) and can
    // never double-release — the slab may already be repinned by a
    // fresh lease when the late ack arrives.
    ASSERT_EQ(0, IciBlockPool::Init());
    const size_t live0 = IciBlockPool::slab_allocated();
    char* data = nullptr;
    IOBuf att;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &att, &data));
    const uint64_t l = block_lease::Pin(std::move(att));
    const int64_t dl = monotonic_time_us() + (int64_t)60e6;
    ASSERT_TRUE(block_lease::Arm(l, 7, dl, 111, /*add_peer=*/false));
    ASSERT_TRUE(block_lease::Arm(l, 7, dl, 222, /*add_peer=*/true));
    // Wrong call id (a forged or cross-call token): frees nothing.
    EXPECT_FALSE(block_lease::ReleaseAcked(l, 8, 222));
    EXPECT_TRUE(block_lease::Alive(l));
    // Right call, NON-entitled peer: frees nothing.
    EXPECT_FALSE(block_lease::ReleaseAcked(l, 7, 999));
    EXPECT_TRUE(block_lease::Alive(l));
    // The winner (the backup try, peer 222) acks: released exactly once.
    EXPECT_TRUE(block_lease::ReleaseAcked(l, 7, 222));
    EXPECT_FALSE(block_lease::Alive(l));
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    // Repin a fresh block — it may reuse the very slab the winner just
    // freed — then deliver the loser's LATE ack (its own peer 111, the
    // ORIGINAL call id): it must find nothing, and the new lease must
    // be untouched even from its own entitled peer under a stale call.
    IOBuf att2;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &att2, &data));
    const uint64_t l2 = block_lease::Pin(std::move(att2));
    ASSERT_TRUE(block_lease::Arm(l2, 9, dl, 111, /*add_peer=*/false));
    EXPECT_FALSE(block_lease::ReleaseAcked(l, 7, 111));   // late loser
    EXPECT_TRUE(block_lease::Alive(l2));
    EXPECT_FALSE(block_lease::ReleaseAcked(l2, 7, 111));  // stale call
    EXPECT_TRUE(block_lease::Alive(l2));
    EXPECT_TRUE(block_lease::ReleaseAcked(l2, 9, 111));
    EXPECT_FALSE(block_lease::ReleaseAcked(l2, 9, 111));  // exactly once
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());
}

// SocketId 0 is a real socket — the first one a process creates, which
// is the server side of echo_bench --ici. The registry once used 0 as
// "no peer", so a response pin armed for it refused every desc_ack and
// stayed pinned until the reaper.
TEST(BlockLease, SocketIdZeroIsARealPeer) {
    ASSERT_EQ(0, IciBlockPool::Init());
    const size_t live0 = IciBlockPool::slab_allocated();
    const int64_t dl = monotonic_time_us() + (int64_t)60e6;
    const uint64_t kSid0 = 0;
    char* data = nullptr;
    IOBuf att;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &att, &data));
    const uint64_t l = block_lease::Pin(std::move(att), "rsp");
    ASSERT_TRUE(block_lease::Arm(l, 5, dl, kSid0));
    // Another connection's ack (token-carrying or not) frees nothing.
    EXPECT_FALSE(block_lease::ReleaseAcked(l, 5, 1));
    EXPECT_FALSE(block_lease::ReleaseAcked(l, 5, block_lease::kNoPeer));
    EXPECT_EQ((size_t)0, block_lease::ReleaseByCall(5, 1));
    EXPECT_TRUE(block_lease::Alive(l));
    // Its own desc_ack does, exactly once.
    EXPECT_TRUE(block_lease::ReleaseAcked(l, 5, kSid0));
    EXPECT_FALSE(block_lease::ReleaseAcked(l, 5, kSid0));

    // Token-less ack and peer death name socket 0 the same way.
    IOBuf att2, att3;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &att2, &data));
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &att3, &data));
    const uint64_t l2 = block_lease::Pin(std::move(att2), "rsp");
    const uint64_t l3 = block_lease::Pin(std::move(att3), "rsp");
    ASSERT_TRUE(block_lease::Arm(l2, 6, dl, kSid0));
    ASSERT_TRUE(block_lease::Arm(l3, 7, dl, kSid0));
    EXPECT_EQ((size_t)1, block_lease::ReleaseByCall(6, kSid0));
    EXPECT_EQ((size_t)1, block_lease::ReleasePeer(kSid0));
    EXPECT_FALSE(block_lease::Alive(l2));
    EXPECT_FALSE(block_lease::Alive(l3));

    // A lease armed with NO peer answers to no connection at all.
    IOBuf att4;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &att4, &data));
    const uint64_t l4 = block_lease::Pin(std::move(att4));
    ASSERT_TRUE(block_lease::Arm(l4, 8, dl, block_lease::kNoPeer));
    EXPECT_FALSE(block_lease::ReleaseAcked(l4, 8, kSid0));
    EXPECT_FALSE(block_lease::ReleaseAcked(l4, 8, block_lease::kNoPeer));
    EXPECT_EQ((size_t)0, block_lease::ReleasePeer(kSid0));
    EXPECT_EQ((size_t)0, block_lease::ReleasePeer(block_lease::kNoPeer));
    EXPECT_TRUE(block_lease::Release(l4));
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());
}

TEST(BlockLease, PeerDeathReleasesOnlyThatPeersPins) {
    ASSERT_EQ(0, IciBlockPool::Init());
    const size_t live0 = IciBlockPool::slab_allocated();
    char* data = nullptr;
    IOBuf a1, a2;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &a1, &data));
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(8000, &a2, &data));
    const uint64_t l1 = block_lease::Pin(std::move(a1));
    const uint64_t l2 = block_lease::Pin(std::move(a2));
    block_lease::Arm(l1, 1, monotonic_time_us() + (int64_t)60e6, 111);
    block_lease::Arm(l2, 2, monotonic_time_us() + (int64_t)60e6, 222);
    // Peer 111 dies: exactly its pin is reclaimed.
    EXPECT_EQ((size_t)1, block_lease::ReleasePeer(111));
    EXPECT_FALSE(block_lease::Alive(l1));
    EXPECT_TRUE(block_lease::Alive(l2));
    EXPECT_EQ((size_t)0, block_lease::ReleasePeer(111));  // idempotent
    EXPECT_TRUE(block_lease::Release(l2));
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());
}

TEST(BlockLease, ControllerReuseReleasesExactlyOnce) {
    ASSERT_EQ(0, IciBlockPool::Init());
    const size_t live0 = IciBlockPool::slab_allocated();
    Controller cntl;
    IOBuf att;
    char* data = nullptr;
    ASSERT_TRUE(IciBlockPool::AllocatePoolAttachment(12000, &att, &data));
    cntl.set_request_pool_attachment(std::move(att));
    ASSERT_TRUE(cntl.has_request_pool_attachment());
    const uint64_t lease = cntl.pool_lease_id();
    ASSERT_NE(0ull, lease);
    EXPECT_EQ(live0 + 1, IciBlockPool::slab_allocated());
    const uint64_t released0 = block_lease::released();
    cntl.Reset();  // reuse ends the previous RPC: the pin must go
    EXPECT_FALSE(cntl.has_request_pool_attachment());
    EXPECT_EQ(released0 + 1, block_lease::released());
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());
    cntl.Reset();  // second Reset: no double release
    EXPECT_EQ(released0 + 1, block_lease::released());
    EXPECT_FALSE(block_lease::Release(lease));
}

TEST(PoolEpoch, StaleEpochFailsOnlyTheCallNotTheConnection) {
    ASSERT_EQ(0, IciBlockPool::Init());
    PoolDescEchoService service;
    Server server;
    ASSERT_EQ(0, server.AddService(&service));
    ASSERT_EQ(0, server.StartNoListen(nullptr));

    IciLink& link = *IciLink::Create();
    SocketOptions sopts;
    sopts.fd = link.second()->event_fd();
    sopts.transport = link.second();
    sopts.owns_transport = true;
    sopts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    sopts.user = server.messenger();
    SocketId server_sid;
    ASSERT_EQ(0, Socket::Create(sopts, &server_sid));
    SocketOptions copts;
    copts.fd = link.first()->event_fd();
    copts.transport = link.first();
    copts.owns_transport = true;
    copts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    copts.user = Channel::client_messenger();
    SocketId client_sid;
    ASSERT_EQ(0, Socket::Create(copts, &client_sid));
    Channel channel;
    ChannelOptions chopts;
    chopts.timeout_ms = 5000;
    ASSERT_EQ(0, channel.InitWithSocketId(client_sid, &chopts));
    test::EchoService_Stub stub(&channel);

    const size_t live0 = IciBlockPool::slab_allocated();
    const uint64_t my_pool = IciBlockPool::pool_id();
    const uint64_t real_epoch = IciBlockPool::pool_epoch();

    // Fence the mapping at a future generation: the in-flight
    // descriptor (minted under real_epoch) must fail with the
    // RETRIABLE stale error — and ONLY the call.
    pool_registry::SetEpoch(my_pool, real_epoch + 7);
    {
        IOBuf att;
        char* data = nullptr;
        ASSERT_TRUE(
            IciBlockPool::AllocatePoolAttachment(20000, &att, &data));
        memset(data, 'e', 20000);
        Controller cntl;
        cntl.set_max_retry(0);  // deterministic: observe the raw fence
        cntl.set_request_pool_attachment(std::move(att));
        test::EchoRequest req;
        test::EchoResponse res;
        req.set_message("stale");
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_TRUE(cntl.Failed());
        EXPECT_EQ(TERR_STALE_EPOCH, cntl.ErrorCode());
    }
    // The pin was released (EndRPC) despite the failure.
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    // Restore the mapping's generation: the SAME connection serves the
    // next descriptor — a stale fence never wedges or kills the link.
    pool_registry::SetEpoch(my_pool, real_epoch);
    {
        IOBuf att;
        char* data = nullptr;
        ASSERT_TRUE(
            IciBlockPool::AllocatePoolAttachment(20000, &att, &data));
        memset(data, 'f', 20000);
        const uint32_t crc = crc32c_extend(0, data, 20000);
        Controller cntl;
        cntl.set_request_pool_attachment(std::move(att));
        test::EchoRequest req;
        test::EchoResponse res;
        req.set_message("fresh");
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_FALSE(cntl.Failed());
        EXPECT_EQ(std::to_string(crc), res.message());
    }
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    SocketUniquePtr cs;
    ASSERT_EQ(0, Socket::AddressSocket(client_sid, &cs));
    cs->SetFailedWithError(TERR_CLOSE);
    cs.reset();
    server.Stop();
    server.Join();
}

TEST(PoolChaos, LeakedPinIsReapedAndStaleInjectionIsRetriable) {
    ASSERT_EQ(0, IciBlockPool::Init());
    PoolDescEchoService service;
    Server server;
    ASSERT_EQ(0, server.AddService(&service));
    ASSERT_EQ(0, server.StartNoListen(nullptr));

    IciLink& link = *IciLink::Create();
    SocketOptions sopts;
    sopts.fd = link.second()->event_fd();
    sopts.transport = link.second();
    sopts.owns_transport = true;
    sopts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    sopts.user = server.messenger();
    SocketId server_sid;
    ASSERT_EQ(0, Socket::Create(sopts, &server_sid));
    SocketOptions copts;
    copts.fd = link.first()->event_fd();
    copts.transport = link.first();
    copts.owns_transport = true;
    copts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    copts.user = Channel::client_messenger();
    SocketId client_sid;
    ASSERT_EQ(0, Socket::Create(copts, &client_sid));
    Channel channel;
    ChannelOptions chopts;
    chopts.timeout_ms = 5000;
    ASSERT_EQ(0, channel.InitWithSocketId(client_sid, &chopts));
    test::EchoService_Stub stub(&channel);

    const size_t live0 = IciBlockPool::slab_allocated();

    // chaos_pool pool_leak=1: EndRPC "forgets" the release; the reaper
    // must reclaim the orphaned pin (the leaked-pin simulation of the
    // soak, deterministic at probability 1).
    ASSERT_TRUE(SetFlagValue("chaos_plan", "pool_leak=1"));
    ASSERT_TRUE(SetFlagValue("chaos_enabled", "1"));
    {
        IOBuf att;
        char* data = nullptr;
        ASSERT_TRUE(
            IciBlockPool::AllocatePoolAttachment(16000, &att, &data));
        memset(data, 'l', 16000);
        Controller cntl;
        cntl.set_timeout_ms(2000);
        cntl.set_request_pool_attachment(std::move(att));
        test::EchoRequest req;
        test::EchoResponse res;
        req.set_message("leak");
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_FALSE(cntl.Failed());
    }
    // The pin leaked past EndRPC...
    EXPECT_EQ(live0 + 1, IciBlockPool::slab_allocated());
    // ...and the reaper reclaims it once the lease (deadline + grace)
    // expires — slab live provably returns to baseline.
    EXPECT_GE(block_lease::ReapExpired(monotonic_time_us() +
                                       (int64_t)3600e6),
              (size_t)1);
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    // chaos_pool pool_stale=1: every resolve answers the retriable
    // stale-epoch fence; the connection survives.
    ASSERT_TRUE(SetFlagValue("chaos_plan", "pool_stale=1"));
    {
        IOBuf att;
        char* data = nullptr;
        ASSERT_TRUE(
            IciBlockPool::AllocatePoolAttachment(16000, &att, &data));
        memset(data, 's', 16000);
        Controller cntl;
        cntl.set_max_retry(0);
        cntl.set_request_pool_attachment(std::move(att));
        test::EchoRequest req;
        test::EchoResponse res;
        req.set_message("stale");
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_TRUE(cntl.Failed());
        EXPECT_EQ(TERR_STALE_EPOCH, cntl.ErrorCode());
    }
    ASSERT_TRUE(SetFlagValue("chaos_enabled", "0"));
    ASSERT_TRUE(SetFlagValue("chaos_plan", ""));
    // Healed: the same connection carries a clean descriptor echo.
    {
        IOBuf att;
        char* data = nullptr;
        ASSERT_TRUE(
            IciBlockPool::AllocatePoolAttachment(16000, &att, &data));
        memset(data, 'h', 16000);
        const uint32_t crc = crc32c_extend(0, data, 16000);
        Controller cntl;
        cntl.set_request_pool_attachment(std::move(att));
        test::EchoRequest req;
        test::EchoResponse res;
        req.set_message("healed");
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_FALSE(cntl.Failed());
        EXPECT_EQ(std::to_string(crc), res.message());
    }
    EXPECT_EQ(live0, IciBlockPool::slab_allocated());

    SocketUniquePtr cs;
    ASSERT_EQ(0, Socket::AddressSocket(client_sid, &cs));
    cs->SetFailedWithError(TERR_CLOSE);
    cs.reset();
    server.Stop();
    server.Join();
}

TEST(DeviceStagingRing, AbortUnblocksParkedAcquireAndTimeoutHolds) {
    ASSERT_EQ(0, IciBlockPool::Init());
    DeviceStagingRing* ring = DeviceStagingRing::Create(1, 8192);
    ASSERT_TRUE(ring != nullptr);
    ASSERT_EQ(0, ring->Acquire(-1));  // window now full
    // Deadline honored: a bounded Acquire on a full window times out
    // instead of wedging (the lost-completion escape).
    const int64_t t0 = monotonic_time_us();
    EXPECT_EQ(-1, ring->Acquire(50 * 1000));
    EXPECT_GE(monotonic_time_us() - t0, (int64_t)45 * 1000);
    // Non-blocking try.
    EXPECT_EQ(-1, ring->Acquire(0));

    // A parked Acquire is unblocked by Abort with -2 (not a timeout).
    std::atomic<int> parked_result{123};
    std::thread waiter([&] {
        parked_result.store(ring->Acquire(10 * 1000 * 1000));
    });
    usleep(50 * 1000);  // let the waiter park
    ring->Abort();
    waiter.join();
    EXPECT_EQ(-2, parked_result.load());
    EXPECT_TRUE(ring->aborted());
    // Future acquires fail fast; in-flight completes still settle.
    EXPECT_EQ(-2, ring->Acquire(-1));
    EXPECT_EQ(0, ring->Complete(0));
    delete ring;
}

// ---------------- full RPC over the link ----------------

namespace {

class IciEchoServiceImpl : public test::EchoService {
public:
    void Echo(google::protobuf::RpcController* cntl_base,
              const test::EchoRequest* req, test::EchoResponse* res,
              google::protobuf::Closure* done) override {
        Controller* cntl = static_cast<Controller*>(cntl_base);
        res->set_message(req->message());
        cntl->response_attachment().append(cntl->request_attachment());
        done->Run();
    }
};

}  // namespace

TEST(IciRpc, EchoOverIciLink) {
    // Server with no TCP listener: the data plane is the ICI link.
    // service declared BEFORE server: ~Server (Stop+Join) must
    // drain handler fibers while the service object is still alive.
    IciEchoServiceImpl service;
    Server server;
    ASSERT_EQ(0, server.AddService(&service));
    ASSERT_EQ(0, server.StartNoListen(nullptr));

    IciLink& link = *IciLink::Create();
    // Server side socket bound to the server's messenger. The sockets own
    // the endpoints: the link frees itself after both recycle.
    SocketOptions sopts;
    sopts.fd = link.second()->event_fd();
    sopts.transport = link.second();
    sopts.owns_transport = true;
    sopts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    sopts.user = server.messenger();
    SocketId server_sid;
    ASSERT_EQ(0, Socket::Create(sopts, &server_sid));

    // Client side socket bound to the client messenger.
    SocketOptions copts;
    copts.fd = link.first()->event_fd();
    copts.transport = link.first();
    copts.owns_transport = true;
    copts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    copts.user = Channel::client_messenger();
    SocketId client_sid;
    ASSERT_EQ(0, Socket::Create(copts, &client_sid));

    Channel channel;
    ChannelOptions chopts;
    chopts.timeout_ms = 5000;
    ASSERT_EQ(0, channel.InitWithSocketId(client_sid, &chopts));
    test::EchoService_Stub stub(&channel);

    // Small sync echo.
    {
        Controller cntl;
        test::EchoRequest req;
        test::EchoResponse res;
        req.set_message("ici says hi");
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_FALSE(cntl.Failed());
        EXPECT_EQ("ici says hi", res.message());
    }
    // 1MB attachment echo (exercises window recycling through the stack).
    {
        Controller cntl;
        test::EchoRequest req;
        test::EchoResponse res;
        req.set_message("big");
        cntl.request_attachment().append(std::string(1u << 20, 'A'));
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_FALSE(cntl.Failed());
        EXPECT_EQ((size_t)(1u << 20), cntl.response_attachment().size());
    }
    // Many pipelined calls.
    {
        struct AsyncCall {
            Controller cntl;
            test::EchoRequest req;
            test::EchoResponse res;
            std::atomic<int>* ok;
            CountdownEvent* pending;
            static void Done(AsyncCall* c) {
                if (!c->cntl.Failed()) c->ok->fetch_add(1);
                c->pending->signal();
                delete c;
            }
        };
        std::atomic<int> ok{0};
        CountdownEvent pending(64);
        for (int i = 0; i < 64; ++i) {
            auto* call = new AsyncCall;
            call->ok = &ok;
            call->pending = &pending;
            call->req.set_message("m" + std::to_string(i));
            stub.Echo(&call->cntl, &call->req, &call->res,
                      google::protobuf::NewCallback(&AsyncCall::Done, call));
        }
        pending.wait();
        EXPECT_EQ(64, ok.load());
    }

    // Teardown: failing the client socket closes the link; the server
    // socket sees EOF and fails too. Join drains server-side fibers that
    // still touch the Server's method map for stats.
    SocketUniquePtr cs;
    ASSERT_EQ(0, Socket::AddressSocket(client_sid, &cs));
    cs->SetFailedWithError(TERR_CLOSE);
    cs.reset();
    server.Stop();
    server.Join();
}

// ---------------- response-direction descriptors (ISSUE 12) -------------

namespace {

// Handler answering desc_rsp:N:S requests with an N-byte pool-block
// reference (pattern: byte 0 = S, rest 'a'+S%26); "inline_fallback"
// exercises the ineligible-shape path (a multi-block IOBuf must fall
// back to inline response-attachment bytes).
class RspDescEchoService : public test::EchoService {
public:
    void Echo(google::protobuf::RpcController* cntl_base,
              const test::EchoRequest* req, test::EchoResponse* res,
              google::protobuf::Closure* done) override {
        Controller* cntl = static_cast<Controller*>(cntl_base);
        unsigned long long n = 0;
        unsigned seed = 0;
        if (sscanf(req->message().c_str(), "desc_rsp:%llu:%u", &n,
                   &seed) == 2 &&
            n > 0) {
            IOBuf out;
            char* data = nullptr;
            if (IciBlockPool::AllocatePoolAttachment((size_t)n, &out,
                                                     &data)) {
                memset(data, 'a' + (int)(seed % 26), (size_t)n);
                data[0] = (char)seed;
                cntl->set_response_pool_attachment(std::move(out));
                res->set_message("ok");
            } else {
                cntl->SetFailed(TERR_RESPONSE, "alloc failed");
            }
        } else if (req->message() == "inline_fallback") {
            // Multi-block shape: one (offset, len) cannot name it, so
            // the set must fall back to inline bytes.
            IOBuf multi;
            multi.append(std::string(9000, 'x'));
            multi.append(std::string(9000, 'y'));
            cntl->set_response_pool_attachment(std::move(multi));
            res->set_message("ok");
        }
        done->Run();
    }
};

}  // namespace

TEST(RspPoolDescriptor, ZeroCopyAndAckLifecycleOverIciLink) {
    ASSERT_EQ(0, IciBlockPool::Init());
    RspDescEchoService service;
    Server server;
    ASSERT_EQ(0, server.AddService(&service));
    ASSERT_EQ(0, server.StartNoListen(nullptr));

    IciLink& link = *IciLink::Create();
    SocketOptions sopts;
    sopts.fd = link.second()->event_fd();
    sopts.transport = link.second();
    sopts.owns_transport = true;
    sopts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    sopts.user = server.messenger();
    SocketId server_sid;
    ASSERT_EQ(0, Socket::Create(sopts, &server_sid));
    SocketOptions copts;
    copts.fd = link.first()->event_fd();
    copts.transport = link.first();
    copts.owns_transport = true;
    copts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    copts.user = Channel::client_messenger();
    SocketId client_sid;
    ASSERT_EQ(0, Socket::Create(copts, &client_sid));
    Channel channel;
    ChannelOptions chopts;
    chopts.timeout_ms = 5000;
    ASSERT_EQ(0, channel.InitWithSocketId(client_sid, &chopts));
    test::EchoService_Stub stub(&channel);

    // The ici tier is descriptor-capable by registry contract — the one
    // seam both descriptor directions consult.
    {
        SocketUniquePtr cs;
        ASSERT_EQ(0, Socket::AddressSocket(client_sid, &cs));
        ASSERT_EQ(TierIci(), cs->transport_tier());
        ASSERT_TRUE(TransportDescriptorCapable(cs.get()));
    }

    const uint64_t pinned0 = block_lease::pinned();
    const size_t kBytes = 60000;
    {
        Controller cntl;
        cntl.set_timeout_ms(5000);
        test::EchoRequest req;
        test::EchoResponse res;
        char ask[64];
        snprintf(ask, sizeof(ask), "desc_rsp:%zu:%u", kBytes, 7u);
        req.set_message(ask);
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_FALSE(cntl.Failed());
        EXPECT_EQ("ok", res.message());
        const Controller::PoolAttachment& view =
            cntl.response_pool_attachment();
        ASSERT_TRUE(view.data != nullptr);
        EXPECT_EQ((uint64_t)kBytes, view.length);
        // Zero inline payload bytes; the view reads the server's pool
        // in place (one address space here, so Contains sees it).
        EXPECT_EQ((size_t)0, cntl.response_attachment().size());
        EXPECT_TRUE(IciBlockPool::Contains(view.data));
        EXPECT_EQ((char)7, view.data[0]);
        EXPECT_EQ((char)('a' + 7), view.data[1]);
        // Client role: no local lease — the pin lives on the SERVER
        // side of the call, held for exactly as long as this view.
        EXPECT_EQ((uint64_t)0, cntl.response_pool_lease_id());
        EXPECT_EQ(pinned0 + 1, block_lease::pinned());
        // Releasing the view (controller reuse) sends the desc_ack; the
        // server's pin must drop exactly once.
        cntl.Reset();
        bool released = false;
        for (int i = 0; i < 500 && !released; ++i) {
            released = block_lease::pinned() == pinned0;
            if (!released) usleep(10 * 1000);
        }
        EXPECT_TRUE(released);
    }
    // Ineligible multi-block shape: transparent inline fallback — the
    // handler API is transport/shape-agnostic.
    {
        Controller cntl;
        cntl.set_timeout_ms(5000);
        test::EchoRequest req;
        test::EchoResponse res;
        req.set_message("inline_fallback");
        stub.Echo(&cntl, &req, &res, nullptr);
        ASSERT_FALSE(cntl.Failed());
        EXPECT_TRUE(cntl.response_pool_attachment().data == nullptr);
        EXPECT_EQ((size_t)18000, cntl.response_attachment().size());
        EXPECT_EQ(pinned0, block_lease::pinned());
    }

    SocketUniquePtr cs;
    ASSERT_EQ(0, Socket::AddressSocket(client_sid, &cs));
    cs->SetFailedWithError(TERR_CLOSE);
    cs.reset();
    server.Stop();
    server.Join();
}

TEST(RspPoolDescriptor, ClientDeathReleasesServerPins) {
    // The chaos-soak invariant at unit scale: a client that dies
    // mid-view (no ack ever sent) must not strand the server's rsp pin
    // — the socket failure observer releases every lease armed against
    // the dead connection (server_call::OnSocketFailed -> ReleasePeer).
    ASSERT_EQ(0, IciBlockPool::Init());
    RspDescEchoService service;
    Server server;
    ASSERT_EQ(0, server.AddService(&service));
    ASSERT_EQ(0, server.StartNoListen(nullptr));

    IciLink& link = *IciLink::Create();
    SocketOptions sopts;
    sopts.fd = link.second()->event_fd();
    sopts.transport = link.second();
    sopts.owns_transport = true;
    sopts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    sopts.user = server.messenger();
    SocketId server_sid;
    ASSERT_EQ(0, Socket::Create(sopts, &server_sid));
    SocketOptions copts;
    copts.fd = link.first()->event_fd();
    copts.transport = link.first();
    copts.owns_transport = true;
    copts.on_edge_triggered_events = InputMessenger::OnNewMessages;
    copts.user = Channel::client_messenger();
    SocketId client_sid;
    ASSERT_EQ(0, Socket::Create(copts, &client_sid));
    Channel channel;
    ChannelOptions chopts;
    chopts.timeout_ms = 5000;
    ASSERT_EQ(0, channel.InitWithSocketId(client_sid, &chopts));
    test::EchoService_Stub stub(&channel);

    const uint64_t pinned0 = block_lease::pinned();
    auto* cntl = new Controller;  // leaked past the socket death below
    cntl->set_timeout_ms(5000);
    test::EchoRequest req;
    test::EchoResponse res;
    req.set_message("desc_rsp:30000:3");
    stub.Echo(cntl, &req, &res, nullptr);
    ASSERT_FALSE(cntl->Failed());
    ASSERT_EQ(pinned0 + 1, block_lease::pinned());

    // "SIGKILL" the client: fail its socket with the view still held
    // and never run the controller's teardown ack.
    SocketUniquePtr cs;
    ASSERT_EQ(0, Socket::AddressSocket(client_sid, &cs));
    cs->SetFailedWithError(TERR_CLOSE);
    cs.reset();
    bool released = false;
    for (int i = 0; i < 500 && !released; ++i) {
        released = block_lease::pinned() == pinned0;
        if (!released) usleep(10 * 1000);
    }
    EXPECT_TRUE(released);
    const uint64_t peer_released0 = block_lease::peer_released();
    EXPECT_GE(peer_released0, (uint64_t)1);

    // The leaked controller's destructor fires a best-effort ack at a
    // dead socket: must be a harmless no-op, not a crash/double free.
    delete cntl;
    server.Stop();
    server.Join();
}
