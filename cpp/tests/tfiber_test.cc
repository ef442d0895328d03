// Fiber runtime tests, mirroring the reference's bthread suite coverage
// (test/bthread_unittest.cpp, butex, mutex, cond, execution_queue,
// work_stealing_queue, ping-pong).
#include <unistd.h>

#include <atomic>
#include <cerrno>
#include <thread>
#include <vector>

#include "tbase/time.h"
#include "tfiber/butex.h"
#include "tfiber/execution_queue.h"
#include "tfiber/fiber.h"
#include "tfiber/fiber_sync.h"
#include "tfiber/work_stealing_queue.h"
#include "ttest/ttest.h"

using namespace tpurpc;

TEST(Fiber, StartJoin) {
    std::atomic<int> x{0};
    fiber_t tid;
    ASSERT_EQ(fiber_start_background(
                  &tid, nullptr,
                  [](void* arg) -> void* {
                      ((std::atomic<int>*)arg)->store(42);
                      return nullptr;
                  },
                  &x),
              0);
    ASSERT_EQ(fiber_join(tid, nullptr), 0);
    EXPECT_EQ(x.load(), 42);
    // Joining a finished fiber returns immediately.
    EXPECT_EQ(fiber_join(tid, nullptr), 0);
    EXPECT_FALSE(fiber_exists(tid));
}

TEST(Fiber, ManyFibers) {
    std::atomic<int> count{0};
    std::vector<fiber_t> tids(500);
    for (auto& tid : tids) {
        ASSERT_EQ(fiber_start_background(
                      &tid, nullptr,
                      [](void* arg) -> void* {
                          ((std::atomic<int>*)arg)->fetch_add(1);
                          fiber_yield();
                          return nullptr;
                      },
                      &count),
                  0);
    }
    for (auto tid : tids) fiber_join(tid, nullptr);
    EXPECT_EQ(count.load(), 500);
}

TEST(Fiber, SelfInsideWorker) {
    fiber_t tid;
    std::atomic<uint64_t> observed{0};
    fiber_start_background(
        &tid, nullptr,
        [](void* arg) -> void* {
            ((std::atomic<uint64_t>*)arg)->store(fiber_self());
            return nullptr;
        },
        &observed);
    fiber_join(tid, nullptr);
    EXPECT_EQ(observed.load(), tid);
    EXPECT_EQ(fiber_self(), INVALID_FIBER);  // not on a worker here
}

TEST(Fiber, Usleep) {
    fiber_t tid;
    std::atomic<int64_t> elapsed{0};
    fiber_start_background(
        &tid, nullptr,
        [](void* arg) -> void* {
            const int64_t t0 = monotonic_time_us();
            fiber_usleep(30000);
            ((std::atomic<int64_t>*)arg)->store(monotonic_time_us() - t0);
            return nullptr;
        },
        &elapsed);
    fiber_join(tid, nullptr);
    EXPECT_GE(elapsed.load(), 25000);
    EXPECT_LT(elapsed.load(), 500000);
}

TEST(Butex, WakeFromPthread) {
    void* b = butex_create();
    butex_word(b)->store(7);
    std::atomic<int> woke{0};
    fiber_t tid;
    struct Ctx {
        void* b;
        std::atomic<int>* woke;
    } ctx{b, &woke};
    fiber_start_background(
        &tid, nullptr,
        [](void* arg) -> void* {
            Ctx* c = (Ctx*)arg;
            while (butex_word(c->b)->load() == 7) {
                butex_wait(c->b, 7, nullptr);
            }
            c->woke->store(1);
            return nullptr;
        },
        &ctx);
    usleep(20000);  // give the fiber time to park
    EXPECT_EQ(woke.load(), 0);
    butex_word(b)->store(8);
    butex_wake(b);
    fiber_join(tid, nullptr);
    EXPECT_EQ(woke.load(), 1);
    butex_destroy(b);
}

TEST(Butex, TimedWaitTimesOut) {
    void* b = butex_create();
    butex_word(b)->store(3);
    fiber_t tid;
    std::atomic<int> rc{-2};
    struct Ctx {
        void* b;
        std::atomic<int>* rc;
    } ctx{b, &rc};
    fiber_start_background(
        &tid, nullptr,
        [](void* arg) -> void* {
            Ctx* c = (Ctx*)arg;
            const int64_t abst = monotonic_time_us() + 20000;
            int r = butex_wait(c->b, 3, &abst);
            c->rc->store(r == ETIMEDOUT ? 1 : 0);
            return nullptr;
        },
        &ctx);
    fiber_join(tid, nullptr);
    EXPECT_EQ(rc.load(), 1);
    butex_destroy(b);
}

TEST(Butex, ValueMismatchReturnsWouldblock) {
    void* b = butex_create();
    butex_word(b)->store(5);
    EXPECT_EQ(butex_wait(b, 99, nullptr), EWOULDBLOCK);
    butex_destroy(b);
}

TEST(Butex, PthreadWaiter) {
    // Wait from a NON-worker pthread; wake from a fiber.
    void* b = butex_create();
    butex_word(b)->store(1);
    std::thread waiter([&] {
        while (butex_word(b)->load() == 1) {
            butex_wait(b, 1, nullptr);
        }
    });
    usleep(10000);
    fiber_t tid;
    fiber_start_background(
        &tid, nullptr,
        [](void* arg) -> void* {
            void* b = arg;
            butex_word(b)->store(2);
            butex_wake_all(b);
            return nullptr;
        },
        b);
    fiber_join(tid, nullptr);
    waiter.join();
    butex_destroy(b);
}

TEST(FiberSync, MutexContention) {
    FiberMutex mu;
    int counter = 0;  // protected by mu
    struct Ctx {
        FiberMutex* mu;
        int* counter;
    } ctx{&mu, &counter};
    std::vector<fiber_t> tids(16);
    for (auto& tid : tids) {
        fiber_start_background(
            &tid, nullptr,
            [](void* arg) -> void* {
                Ctx* c = (Ctx*)arg;
                for (int i = 0; i < 100; ++i) {
                    c->mu->lock();
                    ++*c->counter;
                    if (i % 10 == 0) fiber_yield();  // hold across yield
                    c->mu->unlock();
                }
                return nullptr;
            },
            &ctx);
    }
    for (auto tid : tids) fiber_join(tid, nullptr);
    EXPECT_EQ(counter, 1600);
}

TEST(FiberSync, CondPingPong) {
    struct Ctx {
        FiberMutex mu;
        FiberCond cond;
        int turn = 0;  // 0: ping's turn, 1: pong's turn
        int rounds = 0;
    } ctx;
    auto body = [](void* arg, int me) {
        Ctx* c = (Ctx*)arg;
        for (int i = 0; i < 50; ++i) {
            c->mu.lock();
            while (c->turn != me) c->cond.wait(c->mu);
            c->turn = 1 - me;
            ++c->rounds;
            c->cond.notify_all();
            c->mu.unlock();
        }
    };
    fiber_t ping, pong;
    struct Thunk {
        void* ctx;
        int me;
        void (*body)(void*, int);
    };
    static auto trampoline = [](void* a) -> void* {
        Thunk* t = (Thunk*)a;
        t->body(t->ctx, t->me);
        return nullptr;
    };
    void (*body_fn)(void*, int) = body;
    Thunk t0{&ctx, 0, body_fn}, t1{&ctx, 1, body_fn};
    fiber_start_background(&ping, nullptr, trampoline, &t0);
    fiber_start_background(&pong, nullptr, trampoline, &t1);
    fiber_join(ping, nullptr);
    fiber_join(pong, nullptr);
    EXPECT_EQ(ctx.rounds, 100);
}

TEST(FiberSync, CountdownFromPthread) {
    CountdownEvent ev(3);
    for (int i = 0; i < 3; ++i) {
        fiber_t tid;
        fiber_start_background(
            &tid, nullptr,
            [](void* arg) -> void* {
                fiber_usleep(5000);
                ((CountdownEvent*)arg)->signal();
                return nullptr;
            },
            &ev);
    }
    EXPECT_EQ(ev.wait(), 0);  // waits on this plain pthread
}

TEST(FiberSync, CountdownTimeout) {
    CountdownEvent ev(1);
    const int64_t abst = monotonic_time_us() + 20000;
    EXPECT_EQ(ev.wait(&abst), ETIMEDOUT);
    ev.signal();
    EXPECT_EQ(ev.wait(), 0);
}

TEST(WSQ, OwnerPushPopThiefSteal) {
    WorkStealingQueue<int> q;
    ASSERT_EQ(q.init(64), 0);
    for (int i = 0; i < 10; ++i) EXPECT_TRUE(q.push(i));
    int v;
    // Owner pops LIFO (bottom).
    EXPECT_TRUE(q.pop(&v));
    EXPECT_EQ(v, 9);
    // Thief steals FIFO (top) from another thread.
    std::atomic<int> stolen{-1};
    std::thread thief([&] {
        int s;
        if (q.steal(&s)) stolen.store(s);
    });
    thief.join();
    EXPECT_EQ(stolen.load(), 0);
    size_t left = 0;
    while (q.pop(&v)) ++left;
    EXPECT_EQ(left, 8u);
}

TEST(WSQ, ConcurrentStealAndPop) {
    WorkStealingQueue<int> q;
    ASSERT_EQ(q.init(2048), 0);
    std::atomic<int64_t> sum{0};
    std::atomic<bool> done{false};
    int64_t expect = 0;
    std::thread thief1([&] {
        int v;
        while (!done.load(std::memory_order_acquire)) {
            if (q.steal(&v)) sum.fetch_add(v);
        }
        while (q.steal(&v)) sum.fetch_add(v);
    });
    for (int round = 0; round < 50; ++round) {
        for (int i = 1; i <= 20; ++i) {
            if (q.push(i)) expect += i;
        }
        int v;
        while (q.pop(&v)) sum.fetch_add(v);
    }
    done.store(true, std::memory_order_release);
    thief1.join();
    EXPECT_EQ(sum.load(), expect);
}

TEST(ExecutionQueue, SerializedFifo) {
    struct Sink {
        std::vector<int> seen;
        std::atomic<int> batches{0};
    } sink;
    ExecutionQueue<int> q;
    q.start(
        [](void* meta, ExecutionQueue<int>::TaskIterator& it) -> int {
            Sink* s = (Sink*)meta;
            for (; it; ++it) s->seen.push_back(*it);
            s->batches.fetch_add(1);
            return 0;
        },
        &sink);
    for (int i = 0; i < 200; ++i) {
        ASSERT_EQ(q.execute(i), 0);
    }
    q.stop();
    q.join();
    ASSERT_EQ(sink.seen.size(), 200u);
    for (int i = 0; i < 200; ++i) EXPECT_EQ(sink.seen[i], i);
    EXPECT_EQ(q.execute(1), -1);  // stopped
}

TEST(ExecutionQueue, MultiProducer) {
    struct Sink {
        std::atomic<int64_t> sum{0};
    } sink;
    ExecutionQueue<int> q;
    q.start(
        [](void* meta, ExecutionQueue<int>::TaskIterator& it) -> int {
            for (; it; ++it) ((Sink*)meta)->sum.fetch_add(*it);
            return 0;
        },
        &sink);
    std::vector<std::thread> producers;
    for (int t = 0; t < 4; ++t) {
        producers.emplace_back([&q] {
            for (int i = 1; i <= 500; ++i) q.execute(i);
        });
    }
    for (auto& t : producers) t.join();
    q.stop();
    q.join();
    EXPECT_EQ(sink.sum.load(), 4 * 500 * 501 / 2);
}

TEST(Fiber, PingPongThroughput) {
    // Cooperative switch benchmark (reference test/bthread_ping_pong.cpp
    // style) — also a smoke test that heavy switching doesn't corrupt state.
    struct Ctx {
        void* b;
        // The two runners may be on two workers at once: a plain int
        // loses increments under load (seen with six suites at a time).
        std::atomic<int> rounds{0};
    } ctx;
    ctx.b = butex_create();
    butex_word(ctx.b)->store(0);
    auto runner = [](void* arg) -> void* {
        Ctx* c = (Ctx*)arg;
        for (int i = 0; i < 2000; ++i) {
            butex_word(c->b)->fetch_add(1);
            ++c->rounds;
            butex_wake(c->b);
            fiber_yield();
        }
        return nullptr;
    };
    fiber_t a, b2;
    fiber_start_background(&a, nullptr, runner, &ctx);
    fiber_start_background(&b2, nullptr, runner, &ctx);
    fiber_join(a, nullptr);
    fiber_join(b2, nullptr);
    EXPECT_EQ(ctx.rounds.load(), 4000);
    butex_destroy(ctx.b);
}

// ---------------- fiber-local storage ----------------
// Reference: src/bthread/key.cpp (bthread_key_create/setspecific;
// KeyTable borrow/return pooling) — values are per-fiber, destructors run
// at fiber exit, deleted keys read null, and keytables recycle across
// fibers without leaking values ("session data reuse").

#include "tfiber/fiber_key.h"
#include "tfiber/task_group.h"
#include "tfiber/task_meta.h"

namespace {
std::atomic<int> g_fls_dtor_runs{0};
void fls_dtor(void* p) {
    g_fls_dtor_runs.fetch_add(1);
    delete (std::string*)p;
}
}  // namespace

TEST(FiberKey, PerFiberValuesAndDtors) {
    fiber_key_t key;
    ASSERT_EQ(0, fiber_key_create(&key, fls_dtor));
    g_fls_dtor_runs.store(0);

    struct Ctx {
        fiber_key_t key;
        std::atomic<int> ok{0};
    } ctx{key, {}};
    std::vector<fiber_t> tids(8);
    for (size_t i = 0; i < tids.size(); ++i) {
        fiber_start_background(
            &tids[i], nullptr,
            [](void* arg) -> void* {
                Ctx* c = (Ctx*)arg;
                // Fresh fiber: no inherited value.
                if (fiber_getspecific(c->key) != nullptr) return nullptr;
                auto* v = new std::string("fiber-" +
                                          std::to_string(fiber_self()));
                fiber_setspecific(c->key, v);
                fiber_usleep(1000);  // park: maybe migrate workers
                auto* got = (std::string*)fiber_getspecific(c->key);
                if (got == v) c->ok.fetch_add(1);
                return nullptr;
            },
            &ctx);
    }
    for (auto tid : tids) fiber_join(tid, nullptr);
    EXPECT_EQ(ctx.ok.load(), 8);
    // Every fiber's destructor ran at exit.
    EXPECT_EQ(g_fls_dtor_runs.load(), 8);
    fiber_key_delete(key);
}

TEST(FiberKey, DeletedKeyReadsNull) {
    fiber_key_t key;
    ASSERT_EQ(0, fiber_key_create(&key, nullptr));
    struct Ctx {
        fiber_key_t key;
        void* before = (void*)1;
        void* stale = (void*)1;
        int stale_set_rc = 0;
        void* after = (void*)1;
    } ctx{key};
    fiber_t tid;
    fiber_start_background(
        &tid, nullptr,
        [](void* arg) -> void* {
            Ctx* c = (Ctx*)arg;
            fiber_setspecific(c->key, (void*)0x1234);
            c->before = fiber_getspecific(c->key);
            fiber_key_delete(c->key);
            // The header's contract: a deleted key handle reads null and
            // rejects writes (validated against the registry's current
            // slot generation).
            c->stale = fiber_getspecific(c->key);
            c->stale_set_rc = fiber_setspecific(c->key, (void*)0x5678);
            // And a RECREATED key on the same slot must never see the
            // previous generation's value.
            fiber_key_t key2;
            fiber_key_create(&key2, nullptr);
            c->after = fiber_getspecific(key2);
            fiber_key_delete(key2);
            return nullptr;
        },
        &ctx);
    fiber_join(tid, nullptr);
    EXPECT_EQ(ctx.before, (void*)0x1234);
    EXPECT_EQ(ctx.stale, nullptr);
    EXPECT_EQ(ctx.stale_set_rc, EINVAL);
    EXPECT_EQ(ctx.after, nullptr);
}

TEST(FiberKey, PthreadFallbackOutsideWorkers) {
    fiber_key_t key;
    ASSERT_EQ(0, fiber_key_create(&key, nullptr));
    EXPECT_EQ(nullptr, fiber_getspecific(key));
    ASSERT_EQ(0, fiber_setspecific(key, (void*)0xabcd));
    EXPECT_EQ((void*)0xabcd, fiber_getspecific(key));
    fiber_key_delete(key);
}

// ---------------- rwlock + once ----------------
// Reference: src/bthread/rwlock.cpp (writer-preferring) + bthread_once.

TEST(FiberRWLock, ReadersShareWriterExcludes) {
    FiberRWLock rw;
    std::atomic<int> readers_in{0};
    std::atomic<int> max_readers{0};
    std::atomic<int64_t> counter{0};
    std::atomic<bool> writer_saw_exclusive{true};

    struct Ctx {
        FiberRWLock* rw;
        std::atomic<int>* readers_in;
        std::atomic<int>* max_readers;
        std::atomic<int64_t>* counter;
        std::atomic<bool>* excl;
    } ctx{&rw, &readers_in, &max_readers, &counter, &writer_saw_exclusive};

    std::vector<fiber_t> tids;
    for (int i = 0; i < 6; ++i) {
        fiber_t tid;
        fiber_start_background(
            &tid, nullptr,
            [](void* arg) -> void* {
                Ctx* c = (Ctx*)arg;
                for (int k = 0; k < 40; ++k) {
                    c->rw->rdlock();
                    const int in = c->readers_in->fetch_add(1) + 1;
                    int mx = c->max_readers->load();
                    while (in > mx &&
                           !c->max_readers->compare_exchange_weak(mx, in)) {
                    }
                    if (in <= 0) c->excl->store(false);
                    fiber_usleep(500);  // hold: readers must overlap
                    c->readers_in->fetch_sub(1);
                    c->rw->rdunlock();
                }
                return nullptr;
            },
            &ctx);
        tids.push_back(tid);
    }
    for (int i = 0; i < 2; ++i) {
        fiber_t tid;
        fiber_start_background(
            &tid, nullptr,
            [](void* arg) -> void* {
                Ctx* c = (Ctx*)arg;
                for (int k = 0; k < 25; ++k) {
                    c->rw->wrlock();
                    // No reader may be inside while the writer holds.
                    if (c->readers_in->load() != 0) c->excl->store(false);
                    c->counter->fetch_add(1);
                    c->rw->wrunlock();
                }
                return nullptr;
            },
            &ctx);
        tids.push_back(tid);
    }
    for (auto tid : tids) fiber_join(tid, nullptr);
    EXPECT_TRUE(writer_saw_exclusive.load());
    EXPECT_EQ(counter.load(), 50);
    EXPECT_GT(max_readers.load(), 1);  // readers actually overlapped
}

namespace {
std::atomic<int> g_once_runs{0};
void once_fn() {
    usleep(20000);  // widen the race window
    g_once_runs.fetch_add(1);
}
}  // namespace

TEST(FiberOnce, RunsExactlyOnceAcrossFibers) {
    FiberOnce once;
    g_once_runs.store(0);
    struct Ctx {
        FiberOnce* once;
        std::atomic<int> after{0};
    } ctx{&once, {}};
    std::vector<fiber_t> tids(8);
    for (auto& tid : tids) {
        fiber_start_background(
            &tid, nullptr,
            [](void* arg) -> void* {
                Ctx* c = (Ctx*)arg;
                c->once->call(once_fn);
                // By the time call() returns, the fn has completed.
                if (g_once_runs.load() == 1) c->after.fetch_add(1);
                return nullptr;
            },
            &ctx);
    }
    for (auto tid : tids) fiber_join(tid, nullptr);
    EXPECT_EQ(g_once_runs.load(), 1);
    EXPECT_EQ(ctx.after.load(), 8);
}

// ---------------- worker tags ----------------
// Reference: bthread_tag_t (types.h:37-39) — nonzero tags get an
// ISOLATED worker pool; tagged work can neither starve nor be starved by
// the default pool, and cross-pool wakeups land on the right pool.

TEST(WorkerTags, TaggedFibersRunOnTheirOwnPool) {
    struct Ctx {
        std::atomic<int> ok{0};
        std::atomic<int> wrong_pool{0};
    } ctx;
    FiberAttr tagged = FIBER_ATTR_NORMAL;
    tagged.tag = 7;
    std::vector<fiber_t> tids(6);
    for (auto& tid : tids) {
        fiber_start_background(
            &tid, &tagged,
            [](void* arg) -> void* {
                Ctx* c = (Ctx*)arg;
                TaskGroup* g = TaskGroup::tls_group();
                if (g == nullptr ||
                    g->control() != TaskControl::of_tag(7)) {
                    c->wrong_pool.fetch_add(1);
                }
                fiber_usleep(2000);  // park + resume: still our pool
                g = TaskGroup::tls_group();
                if (g == nullptr ||
                    g->control() != TaskControl::of_tag(7)) {
                    c->wrong_pool.fetch_add(1);
                    return nullptr;
                }
                c->ok.fetch_add(1);
                return nullptr;
            },
            &ctx);
    }
    for (auto tid : tids) fiber_join(tid, nullptr);
    EXPECT_EQ(ctx.ok.load(), 6);
    EXPECT_EQ(ctx.wrong_pool.load(), 0);
}

TEST(WorkerTags, TaggedPoolNotStarvedByDefaultPool) {
    // Saturate the DEFAULT pool with spinning fibers; a tagged fiber must
    // still make progress promptly on its own workers.
    std::atomic<bool> stop{false};
    std::vector<fiber_t> hogs(16);
    for (auto& tid : hogs) {
        fiber_start_background(
            &tid, nullptr,
            [](void* arg) -> void* {
                auto* s = (std::atomic<bool>*)arg;
                while (!s->load(std::memory_order_relaxed)) {
                    // busy spin with occasional yield: keeps default
                    // workers saturated.
                    for (volatile int i = 0; i < 20000; ++i) {
                    }
                    fiber_yield();
                }
                return nullptr;
            },
            &stop);
    }
    FiberAttr tagged = FIBER_ATTR_NORMAL;
    tagged.tag = 9;
    std::atomic<int64_t> latency_us{-1};
    struct Ctx {
        std::atomic<int64_t>* lat;
        int64_t t0;
    } ctx{&latency_us, monotonic_time_us()};
    fiber_t tid;
    fiber_start_background(
        &tid, &tagged,
        [](void* arg) -> void* {
            Ctx* c = (Ctx*)arg;
            c->lat->store(monotonic_time_us() - c->t0);
            return nullptr;
        },
        &ctx);
    fiber_join(tid, nullptr);
    stop.store(true);
    for (auto t : hogs) fiber_join(t, nullptr);
    EXPECT_GE(latency_us.load(), 0);
    // Scheduled on its own pool: starts quickly despite the saturated
    // default pool (generous bound for the 1-core CI box).
    EXPECT_LT(latency_us.load(), 200 * 1000);
}

// ---------------- urgent scheduling + pool growth + remote queue ----------------
// Reference: src/bthread/task_group.cpp start_foreground (run the new
// bthread immediately, requeue the caller), TaskControl::add_workers,
// remote_task_queue.h.

#include "tbase/flags.h"
#include "tbase/mpmc_queue.h"

DECLARE_int32(fiber_tagged_worker_count);

TEST(FiberUrgent, ChildRunsBeforeCallerResumes) {
    // A single-worker tagged pool makes the ordering deterministic: the
    // lone worker must run the urgent child before it can resume the
    // requeued caller.
    FLAGS_fiber_tagged_worker_count.set(1);
    FiberAttr tagged = FIBER_ATTR_NORMAL;
    tagged.tag = 11;  // fresh tag: pool starts now, with 1 worker
    struct Ctx {
        std::atomic<int> seq{0};
        int child_at = -1;
        int caller_resumed_at = -1;
        FiberAttr attr;
    } ctx;
    ctx.attr = tagged;
    fiber_t outer;
    fiber_start_background(
        &outer, &tagged,
        [](void* arg) -> void* {
            Ctx* c = (Ctx*)arg;
            fiber_t child;
            struct Inner {
                Ctx* c;
            } inner{c};
            fiber_start_urgent(
                &child, &c->attr,
                [](void* a) -> void* {
                    Ctx* c = ((Inner*)a)->c;
                    c->child_at = c->seq.fetch_add(1);
                    return nullptr;
                },
                &inner);
            c->caller_resumed_at = c->seq.fetch_add(1);
            fiber_join(child, nullptr);
            return nullptr;
        },
        &ctx);
    fiber_join(outer, nullptr);
    FLAGS_fiber_tagged_worker_count.set(2);
    ASSERT_GE(ctx.child_at, 0);
    ASSERT_GE(ctx.caller_resumed_at, 0);
    EXPECT_LT(ctx.child_at, ctx.caller_resumed_at);
}

TEST(TaskControlGrowth, SetConcurrencyAddsWorkersAfterStart) {
    TaskControl* c = TaskControl::singleton();
    c->ensure_started();
    const int before = c->concurrency();
    c->set_concurrency(before + 2);
    EXPECT_EQ(c->concurrency(), before + 2);
    // The grown pool still schedules: run a burst of fibers to completion.
    std::atomic<int> done{0};
    std::vector<fiber_t> tids(64);
    for (auto& tid : tids) {
        fiber_start_background(
            &tid, nullptr,
            [](void* arg) -> void* {
                ((std::atomic<int>*)arg)->fetch_add(1);
                return nullptr;
            },
            &done);
    }
    for (auto tid : tids) fiber_join(tid, nullptr);
    EXPECT_EQ(done.load(), 64);
    // Shrink is a documented no-op.
    c->set_concurrency(1);
    EXPECT_EQ(c->concurrency(), before + 2);
}

TEST(TaskControlGrowth, RemoteSpawnBurstFromPthreads) {
    // Hammer the lock-free remote ring (and its overflow spill) from
    // plain pthreads: every spawn goes through ready_to_run_remote.
    std::atomic<int> done{0};
    std::vector<std::thread> producers;
    std::vector<std::vector<fiber_t>> tids(4, std::vector<fiber_t>(2000));
    for (int t = 0; t < 4; ++t) {
        producers.emplace_back([&, t] {
            for (auto& tid : tids[t]) {
                while (fiber_start_background(
                           &tid, nullptr,
                           [](void* arg) -> void* {
                               ((std::atomic<int>*)arg)->fetch_add(1);
                               return nullptr;
                           },
                           &done) != 0) {
                    std::this_thread::yield();
                }
            }
        });
    }
    for (auto& p : producers) p.join();
    for (auto& v : tids) {
        for (auto tid : v) fiber_join(tid, nullptr);
    }
    EXPECT_EQ(done.load(), 8000);
}

TEST(MpmcQueue, ConcurrentSumConserved) {
    MpmcBoundedQueue<int> q;
    ASSERT_EQ(0, q.init(256));
    EXPECT_NE(0, q.init(100));  // non-power-of-two rejected
    ASSERT_EQ(0, q.init(256));
    constexpr int kPerProducer = 20000;
    std::atomic<int64_t> popped_sum{0};
    std::atomic<int> popped_n{0};
    std::atomic<bool> done_producing{false};
    std::vector<std::thread> threads;
    for (int p = 0; p < 2; ++p) {
        threads.emplace_back([&, p] {
            for (int i = 0; i < kPerProducer; ++i) {
                const int v = p * kPerProducer + i + 1;
                while (!q.push(v)) std::this_thread::yield();
            }
        });
    }
    for (int cix = 0; cix < 2; ++cix) {
        threads.emplace_back([&] {
            int v;
            while (true) {
                if (q.pop(&v)) {
                    popped_sum.fetch_add(v);
                    popped_n.fetch_add(1);
                } else if (done_producing.load() &&
                           popped_n.load() == 2 * kPerProducer) {
                    return;
                } else {
                    std::this_thread::yield();
                }
            }
        });
    }
    threads[0].join();
    threads[1].join();
    done_producing.store(true);
    threads[2].join();
    threads[3].join();
    const int64_t n = 2 * kPerProducer;
    EXPECT_EQ(popped_n.load(), n);
    EXPECT_EQ(popped_sum.load(), n * (n + 1) / 2);
}

// ---------------- TaskTracer (reference bthread/task_tracer.h) ----------------

#include "tfiber/task_tracer.h"

TEST(TaskTracer, ParkedFiberStackShowsParkSite) {
    // A fiber parked in fiber_usleep: its dumped stack must contain its
    // park site (sched_park / usleep frames) and its body function.
    std::atomic<bool> parked{false};
    std::atomic<bool> release{false};
    struct Ctx {
        std::atomic<bool>* parked;
        std::atomic<bool>* release;
    } ctx{&parked, &release};
    fiber_t tid;
    fiber_start_background(
        &tid, nullptr,
        [](void* arg) -> void* {
            Ctx* c = (Ctx*)arg;
            c->parked->store(true);
            while (!c->release->load()) {
                fiber_usleep(50 * 1000);
            }
            return nullptr;
        },
        &ctx);
    while (!parked.load()) fiber_usleep(1000);
    fiber_usleep(20 * 1000);  // let it reach the park
    const std::string dump = DumpFiberStacks();
    release.store(true);
    fiber_join(tid, nullptr);
    EXPECT_NE(dump.find("live fiber"), std::string::npos);
    EXPECT_NE(dump.find("[suspended]"), std::string::npos);
    // The park site: the saved RIP points into the suspend machinery
    // (sched_park is the direct tf_jump_fcontext caller; usleep frames
    // follow on the fp chain).
    const bool has_park =
        dump.find("sched_park") != std::string::npos ||
        dump.find("usleep") != std::string::npos;
    EXPECT_TRUE(has_park);
}
