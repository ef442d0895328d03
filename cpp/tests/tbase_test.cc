// Unit tests for tbase, mirroring the reference's butil test coverage
// (test/iobuf_unittest.cpp, test/resource_pool_unittest.cpp,
// test/flat_map_unittest.cpp, test/endpoint_unittest.cpp et al).
#include <unistd.h>

#include <atomic>
#include <thread>
#include <vector>

#include "tbase/crc32c.h"
#include "tbase/doubly_buffered_data.h"
#include "tbase/endpoint.h"
#include "tbase/fast_rand.h"
#include "tbase/flags.h"
#include "tbase/flat_map.h"
#include "tbase/iobuf.h"
#include "tbase/logging.h"
#include "tbase/resource_pool.h"
#include "tbase/time.h"
#include "tbase/versioned_ref.h"
#include "ttest/ttest.h"

using namespace tpurpc;

TEST(IOBuf, AppendAndRead) {
    IOBuf buf;
    EXPECT_TRUE(buf.empty());
    buf.append("hello ");
    buf.append(std::string("world"));
    EXPECT_EQ(buf.size(), 11u);
    EXPECT_EQ(buf.to_string(), "hello world");
    EXPECT_TRUE(buf.equals("hello world"));
    EXPECT_EQ(buf.front_byte(), 'h');
}

TEST(IOBuf, LargeAppendSpansBlocks) {
    IOBuf buf;
    std::string big(100000, 'x');
    for (size_t i = 0; i < big.size(); ++i) big[i] = (char)('a' + i % 26);
    buf.append(big);
    EXPECT_EQ(buf.size(), big.size());
    EXPECT_GT(buf.backing_block_num(), 1u);
    EXPECT_EQ(buf.to_string(), big);
}

TEST(IOBuf, CutnZeroCopy) {
    IOBuf buf;
    std::string data(50000, 'q');
    buf.append(data);
    IOBuf head;
    size_t moved = buf.cutn(&head, 20000);
    EXPECT_EQ(moved, 20000u);
    EXPECT_EQ(head.size(), 20000u);
    EXPECT_EQ(buf.size(), 30000u);
    EXPECT_EQ(head.to_string(), std::string(20000, 'q'));
    EXPECT_EQ(buf.to_string(), std::string(30000, 'q'));
}

TEST(IOBuf, CutIntoBuffer) {
    IOBuf buf;
    buf.append("abcdefgh");
    char tmp[4];
    EXPECT_EQ(buf.cutn(tmp, 4), 4u);
    EXPECT_EQ(std::string(tmp, 4), "abcd");
    EXPECT_EQ(buf.to_string(), "efgh");
    char c;
    EXPECT_EQ(buf.cut1(&c), 0);
    EXPECT_EQ(c, 'e');
}

TEST(IOBuf, PopFrontBack) {
    IOBuf buf;
    buf.append("0123456789");
    EXPECT_EQ(buf.pop_front(3), 3u);
    EXPECT_EQ(buf.pop_back(2), 2u);
    EXPECT_EQ(buf.to_string(), "34567");
}

TEST(IOBuf, ZeroCopyAppendSharesBlocks) {
    IOBuf a;
    a.append(std::string(10000, 'z'));
    IOBuf b;
    b.append(a);  // zero-copy ref share
    EXPECT_EQ(a.size(), b.size());
    a.clear();
    EXPECT_EQ(b.to_string(), std::string(10000, 'z'));  // b keeps blocks alive
}

TEST(IOBuf, CopyToWithOffset) {
    IOBuf buf;
    buf.append("hello world");
    std::string s;
    buf.copy_to(&s, 5, 6);
    EXPECT_EQ(s, "world");
    EXPECT_EQ(buf.size(), 11u);  // copy_to doesn't consume
}

// ISSUE 33: one chunk of a request attachment goes into a staging slot by
// ONE walk from the chunk's offset: blocks straddling the offset and the
// end, a zero tail where the buffer ends first, and the crc32c of all of
// dst, whatever the cut.
TEST(IOBuf, CopyToCrc32cFromAnOffset) {
    IOBuf buf;
    std::string all;
    for (int i = 0; i < 5; ++i) {  // odd lengths over several blocks
        IOBuf piece;
        piece.append(std::string(6000 + 7 * i, (char)('a' + i)));
        buf.append(piece);
        all += piece.to_string();
    }
    ASSERT_TRUE(buf.backing_block_num() >= 4u);
    const size_t cuts[][2] = {{0, all.size()}, {0, 100}, {5990, 40},
                              {6000, 6007}, {5, 20000}, {25000, 6000},
                              {all.size() - 1, 8}, {all.size(), 16},
                              {all.size() + 50, 16}, {100, 0}};
    for (const auto& cut : cuts) {
        const size_t pos = cut[0], cap = cut[1];
        std::string want = pos < all.size() ? all.substr(pos, cap) : "";
        const size_t from_buf = want.size();
        want.resize(cap, '\0');
        std::string got(cap + 4, '#');
        size_t copied = 12345;
        const uint32_t crc = buf.copy_to_crc32c(&got[0], cap, pos, &copied);
        EXPECT_EQ(copied, from_buf);
        EXPECT_EQ(got.substr(0, cap), want);
        EXPECT_EQ(got.substr(cap), "####");  // nothing past cap is touched
        EXPECT_EQ(crc, crc32c(want.data(), want.size()));
    }
    EXPECT_EQ(buf.size(), all.size());  // nothing was consumed
    // The chunks of a call, laid end to end, are the call: a reader that
    // walks 4096 at a time sees the same bytes as one that takes it whole.
    std::string whole(16384, '#'), chunked(16384, '#');
    buf.copy_to_crc32c(&whole[0], whole.size(), 0, nullptr);
    for (size_t off = 0; off < chunked.size(); off += 4096) {
        buf.copy_to_crc32c(&chunked[off], 4096, off, nullptr);
    }
    EXPECT_EQ(whole, chunked);
}

TEST(IOBuf, MoveSemantics) {
    IOBuf a;
    a.append("data");
    IOBuf b(std::move(a));
    EXPECT_EQ(b.to_string(), "data");
    EXPECT_TRUE(a.empty());
    IOBuf c;
    c = std::move(b);
    EXPECT_EQ(c.to_string(), "data");
}

TEST(IOBuf, ManyRefsGrowToBigView) {
    IOBuf buf;
    IOBuf scraps;
    // Force many non-mergeable refs by cutting from different bufs.
    std::string expect;
    for (int i = 0; i < 50; ++i) {
        IOBuf tmp;
        std::string piece(100, (char)('a' + i % 26));
        tmp.append(piece);
        expect += piece;
        buf.append(tmp);
    }
    EXPECT_EQ(buf.to_string(), expect);
    IOBuf out;
    buf.cutn(&out, expect.size() / 2);
    EXPECT_EQ(out.to_string() + buf.to_string(), expect);
}

TEST(IOBuf, FdRoundTrip) {
    int fds[2];
    ASSERT_EQ(pipe(fds), 0);
    IOBuf out;
    std::string payload(60000, 'p');
    out.append(payload);
    size_t total_written = 0;
    while (total_written < payload.size()) {
        // Drain concurrently to avoid pipe-buffer deadlock.
        ssize_t w = out.cut_into_file_descriptor(fds[1], 16384);
        ASSERT_GT(w, 0);
        total_written += (size_t)w;
        IOPortal in;
        ssize_t r = in.append_from_file_descriptor(fds[0], 65536);
        ASSERT_GT(r, 0);
        EXPECT_EQ(in.to_string(), std::string((size_t)r, 'p'));
    }
    close(fds[0]);
    close(fds[1]);
}

TEST(IOBuf, PortalAccumulates) {
    int fds[2];
    ASSERT_EQ(pipe(fds), 0);
    IOPortal in;
    std::string sent;
    for (int i = 0; i < 10; ++i) {
        std::string chunk(1000, (char)('0' + i));
        ASSERT_EQ(write(fds[1], chunk.data(), chunk.size()),
                  (ssize_t)chunk.size());
        sent += chunk;
        ASSERT_GT(in.append_from_file_descriptor(fds[0], 65536), 0);
    }
    EXPECT_EQ(in.to_string(), sent);
    close(fds[0]);
    close(fds[1]);
}

TEST(ResourcePool, GetAddressReturn) {
    struct Obj {
        int x;
    };
    ResourceId id1, id2;
    Obj* o1 = get_resource<Obj>(&id1);
    ASSERT_TRUE(o1 != nullptr);
    o1->x = 42;
    Obj* o2 = get_resource<Obj>(&id2);
    ASSERT_TRUE(o2 != nullptr);
    EXPECT_NE(o1, o2);
    EXPECT_EQ(address_resource<Obj>(id1), o1);
    EXPECT_EQ(address_resource<Obj>(id1)->x, 42);
    return_resource<Obj>(id1);
    // Slot gets recycled.
    ResourceId id3;
    Obj* o3 = get_resource<Obj>(&id3);
    EXPECT_EQ(o3, o1);
    return_resource<Obj>(id2);
    return_resource<Obj>(id3);
}

struct TestVRef : public VersionedRefWithId<TestVRef> {
    int failed_count = 0;
    int recycled_count = 0;
    void OnFailed() { ++failed_count; }
    void OnRecycle() { ++recycled_count; }
};

TEST(VersionedRef, Lifecycle) {
    VRefId id;
    TestVRef* obj = nullptr;
    ASSERT_EQ(TestVRef::Create(&id, &obj), 0);
    obj->failed_count = 0;
    obj->recycled_count = 0;
    EXPECT_EQ(obj->nref(), 1);

    TestVRef* addr = TestVRef::Address(id);
    ASSERT_TRUE(addr == obj);
    EXPECT_EQ(obj->nref(), 2);

    EXPECT_EQ(obj->SetFailed(), 0);
    EXPECT_EQ(obj->failed_count, 1);
    EXPECT_EQ(obj->SetFailed(), -1);  // second failure is a no-op
    EXPECT_TRUE(obj->Failed());

    // Stale address after failure.
    EXPECT_TRUE(TestVRef::Address(id) == nullptr);

    EXPECT_EQ(obj->recycled_count, 0);
    obj->Dereference();  // drop our Address ref -> recycle
    EXPECT_EQ(obj->recycled_count, 1);

    // Slot is reusable with a new even version; old id stays dead.
    VRefId id2;
    TestVRef* obj2 = nullptr;
    ASSERT_EQ(TestVRef::Create(&id2, &obj2), 0);
    EXPECT_NE(id2, id);
    EXPECT_TRUE(TestVRef::Address(id) == nullptr);
    TestVRef* a2 = TestVRef::Address(id2);
    EXPECT_TRUE(a2 == obj2);
    a2->Dereference();
    obj2->SetFailed();
}

TEST(FlatMap, Basics) {
    FlatMap<std::string, int> m;
    EXPECT_TRUE(m.seek("a") == nullptr);
    m["a"] = 1;
    m["b"] = 2;
    EXPECT_EQ(m.size(), 2u);
    EXPECT_EQ(*m.seek("a"), 1);
    m["a"] = 10;
    EXPECT_EQ(*m.seek("a"), 10);
    EXPECT_EQ(m.erase("a"), 1u);
    EXPECT_TRUE(m.seek("a") == nullptr);
    EXPECT_EQ(m.size(), 1u);
}

TEST(FlatMap, GrowthKeepsEntries) {
    FlatMap<int, int> m;
    for (int i = 0; i < 1000; ++i) m[i] = i * 7;
    EXPECT_EQ(m.size(), 1000u);
    for (int i = 0; i < 1000; ++i) {
        int* v = m.seek(i);
        ASSERT_TRUE(v != nullptr);
        EXPECT_EQ(*v, i * 7);
    }
}

TEST(FlatMap, EraseChurnDoesNotDegrade) {
    // Regression: tombstone accumulation must trigger rehash, not an
    // unbounded/never-ending probe loop.
    FlatMap<int, int> m;
    for (int round = 0; round < 200; ++round) {
        for (int i = 0; i < 10; ++i) m[round * 10 + i] = i;
        for (int i = 0; i < 10; ++i) {
            EXPECT_EQ(m.erase(round * 10 + i), 1u);
        }
    }
    EXPECT_EQ(m.size(), 0u);
    m[12345] = 1;
    EXPECT_EQ(*m.seek(12345), 1);
}

TEST(FlatMap, CaseIgnored) {
    CaseIgnoredFlatMap<int> m;
    m["Content-Type"] = 5;
    EXPECT_TRUE(m.seek("content-type") != nullptr);
    EXPECT_EQ(*m.seek("CONTENT-TYPE"), 5);
}

TEST(EndPoint, ParseFormat) {
    EndPoint ep;
    ASSERT_EQ(str2endpoint("127.0.0.1:8080", &ep), 0);
    EXPECT_EQ(ep.port, 8080);
    EXPECT_EQ(endpoint2str(ep), "127.0.0.1:8080");
    EXPECT_NE(str2endpoint("not an endpoint", &ep), 0);
    EXPECT_NE(str2endpoint("1.2.3.4:99999", &ep), 0);
    ASSERT_EQ(hostname2endpoint("localhost:80", &ep), 0);
    EXPECT_EQ(ep.port, 80);
}

TEST(DoublyBufferedData, ReadModify) {
    DoublyBufferedData<std::vector<int>> dbd;
    dbd.Modify([](std::vector<int>& v) {
        v.push_back(42);
        return true;
    });
    {
        DoublyBufferedData<std::vector<int>>::ScopedPtr ptr;
        ASSERT_EQ(dbd.Read(&ptr), 0);
        ASSERT_EQ(ptr->size(), 1u);
        EXPECT_EQ((*ptr)[0], 42);
    }
    // Concurrent readers while modifying.
    std::atomic<bool> stop{false};
    std::thread reader([&] {
        while (!stop.load()) {
            DoublyBufferedData<std::vector<int>>::ScopedPtr ptr;
            dbd.Read(&ptr);
            if (!ptr->empty()) {
                volatile int x = (*ptr)[0];
                (void)x;
            }
        }
    });
    for (int i = 0; i < 100; ++i) {
        dbd.Modify([i](std::vector<int>& v) {
            v.assign(3, i);
            return true;
        });
    }
    stop = true;
    reader.join();
    DoublyBufferedData<std::vector<int>>::ScopedPtr ptr;
    dbd.Read(&ptr);
    EXPECT_EQ(ptr->size(), 3u);
}

DEFINE_int32(test_flag_int, 7, "test flag");
DEFINE_bool(test_flag_bool, false, "test flag");
DEFINE_string(test_flag_str, "abc", "test flag");

TEST(Flags, DefineFindSet) {
    EXPECT_EQ(FLAGS_test_flag_int.get(), 7);
    EXPECT_TRUE(SetFlagValue("test_flag_int", "99"));
    EXPECT_EQ(FLAGS_test_flag_int.get(), 99);
    EXPECT_FALSE(SetFlagValue("test_flag_int", "not_a_number"));
    EXPECT_EQ(FLAGS_test_flag_int.get(), 99);
    EXPECT_FALSE(SetFlagValue("no_such_flag", "1"));
    EXPECT_TRUE(SetFlagValue("test_flag_bool", "true"));
    EXPECT_TRUE(FLAGS_test_flag_bool.get());
    EXPECT_TRUE(SetFlagValue("test_flag_str", "xyz"));
    EXPECT_EQ(FLAGS_test_flag_str.get(), "xyz");
    FLAGS_test_flag_int.set_validator([](int32_t v) { return v < 100; });
    EXPECT_FALSE(SetFlagValue("test_flag_int", "500"));
    EXPECT_TRUE(SetFlagValue("test_flag_int", "50"));
    EXPECT_EQ(FLAGS_test_flag_int.get(), 50);
}

TEST(Misc, FastRandAndTime) {
    uint64_t a = fast_rand();
    uint64_t b = fast_rand();
    EXPECT_NE(a, b);
    for (int i = 0; i < 100; ++i) {
        EXPECT_LT(fast_rand_less_than(10), 10u);
        double d = fast_rand_double();
        EXPECT_GE(d, 0.0);
        EXPECT_LT(d, 1.0);
    }
    int64_t t0 = monotonic_time_us();
    int64_t w0 = gettimeofday_us();
    EXPECT_GT(t0, 0);
    EXPECT_GT(w0, 0);
    EXPECT_GT(ticks_per_us(), 0.0);
}

// ---------------- ResourcePool TLS free chunks ----------------
// Reference resource_pool_inl.h: per-thread free chunks; a live id is
// never handed to two owners concurrently.

namespace {
struct PoolItem {
    std::atomic<int> owner{0};
};
}  // namespace

TEST(ResourcePool, TlsChunksNoDoubleOwnership) {
    std::atomic<bool> stop{false};
    std::atomic<int> violations{0};
    auto worker = [&](int me) {
        std::vector<ResourceId> held;
        uint64_t rng = (uint64_t)me * 2654435761u + 1;
        while (!stop.load(std::memory_order_relaxed)) {
            rng = rng * 6364136223846793005ull + 1442695040888963407ull;
            if ((rng >> 33) % 2 == 0 || held.empty()) {
                ResourceId id;
                PoolItem* it = get_resource<PoolItem>(&id);
                if (it == nullptr) continue;
                int expected = 0;
                if (!it->owner.compare_exchange_strong(expected, me)) {
                    violations.fetch_add(1);  // someone else owns this slot!
                }
                held.push_back(id);
            } else {
                const ResourceId id = held.back();
                held.pop_back();
                PoolItem* it = address_resource<PoolItem>(id);
                it->owner.store(0);
                return_resource<PoolItem>(id);
            }
            if (held.size() > 300) {
                for (ResourceId id : held) {
                    address_resource<PoolItem>(id)->owner.store(0);
                    return_resource<PoolItem>(id);
                }
                held.clear();
            }
        }
        for (ResourceId id : held) {
            address_resource<PoolItem>(id)->owner.store(0);
            return_resource<PoolItem>(id);
        }
    };
    std::vector<std::thread> threads;
    for (int i = 1; i <= 4; ++i) threads.emplace_back(worker, i);
    usleep(300 * 1000);
    stop.store(true);
    for (auto& t : threads) t.join();
    EXPECT_EQ(violations.load(), 0);
}

TEST(Logging, RateLimitedMacros) {
    // Compile + semantics: LOG_EVERY_N passes on iterations 0, n, 2n...
    // and LOG_EVERY_SECOND at most once per second (asserted via the
    // sink capture).
    std::atomic<int> captured{0};
    SetLogSink([&](int, const char*, int, const std::string&) {
        captured.fetch_add(1);
        return true;  // suppress stderr
    });
    for (int i = 0; i < 10; ++i) {
        LOG_EVERY_N(ERROR, 5) << "every-5 " << i;
    }
    EXPECT_EQ(captured.load(), 2);  // i=0 and i=5
    captured.store(0);
    for (int i = 0; i < 100; ++i) {
        LOG_EVERY_SECOND(ERROR) << "every-second " << i;
    }
    EXPECT_EQ(captured.load(), 1);
    SetLogSink(nullptr);
}
