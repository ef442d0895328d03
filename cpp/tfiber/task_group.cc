#include "tfiber/fiber_key.h"
#include "tfiber/task_group.h"

#include <pthread.h>
#include <unistd.h>

#include <algorithm>
#include <map>
#include <cerrno>
#include <thread>

#include "tbase/fast_rand.h"
#include "tbase/time.h"
#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/logging.h"
#include "tbase/resource_pool.h"
#include "tfiber/butex.h"
#include "tfiber/timer_thread.h"
#include "tvar/multi_dimension.h"
#include "tvar/reducer.h"
#include "tvar/stage_recorder.h"

// 0 = auto: hardware_concurrency + 1, min 4 (the reference defaults to
// cores+1 via FLAGS_bthread_concurrency; a fixed count would cap
// throughput on many-core TPU-VM hosts).
DEFINE_int32(fiber_worker_count, 0, "number of fiber worker pthreads");
DEFINE_int32(fiber_tagged_worker_count, 2,
             "worker pthreads per nonzero worker tag pool");

namespace tpurpc {

namespace {
thread_local TaskGroup* tls_task_group = nullptr;

// Scheduler telemetry families, one series per worker pool
// ({pool="tag"}). Created on first pool start (runtime, never
// static-init); the /loops builtin and the series rings read them.
LabelledMetric<IntCell>* sched_steals() {
    static auto* m =
        new LabelledMetric<IntCell>("rpc_scheduler_steals", {"pool"});
    return m;
}
LabelledMetric<IntCell>* sched_remote_overflows() {
    static auto* m = new LabelledMetric<IntCell>(
        "rpc_scheduler_remote_overflows", {"pool"});
    return m;
}
LabelledMetric<IntCell>* sched_urgent() {
    static auto* m = new LabelledMetric<IntCell>(
        "rpc_scheduler_urgent_handoffs", {"pool"});
    return m;
}
LabelledMetric<IntCell>* sched_rq_highwater() {
    static auto* m = new LabelledMetric<IntCell>(
        "rpc_scheduler_runqueue_highwater", {"pool"});
    return m;
}

// Safety-net counters (plain cumulative /vars integers): how often the
// worker park's 100 ms timeout, and no signal, ended a park -- and how
// often the worker then found a runnable fiber, i.e. a wake-up was lost.
LazyAdder g_park_timeouts("rpc_scheduler_park_timeouts");
LazyAdder g_park_timeouts_found_work(
    "rpc_scheduler_park_timeouts_found_work");
}  // namespace

TaskGroup* TaskGroup::tls_group() { return tls_task_group; }

bool is_running_on_fiber_worker() {
    TaskGroup* g = tls_task_group;
    return g != nullptr && g->current() != nullptr;
}

// ---------------- ASan fiber-switch annotations ----------------
// Without these, ASan keeps using the OLD stack's bounds after a context
// switch and reports wild stack-buffer-underflow/overflow (the reference
// carries the same annotations in src/bthread/stack_inl.h).
// The fake-stack handle of each context must be saved at switch-out and
// handed back at switch-in (a null save tells ASan the context is DYING
// and frees its fake frames — only exit_current may pass null).
#ifndef __has_feature
#define __has_feature(x) 0  // gcc signals ASan via __SANITIZE_ADDRESS__
#endif
#if defined(__SANITIZE_ADDRESS__) || __has_feature(address_sanitizer)
extern "C" {
void __sanitizer_start_switch_fiber(void** fake_stack_save,
                                    const void* bottom, size_t size);
void __sanitizer_finish_switch_fiber(void* fake_stack_save,
                                     const void** bottom_old,
                                     size_t* size_old);
}
static void asan_before_jump(void** fake_save, const void* bottom,
                             size_t size) {
    __sanitizer_start_switch_fiber(fake_save, bottom, size);
}
static void asan_after_jump(void* fake_restore) {
    __sanitizer_finish_switch_fiber(fake_restore, nullptr, nullptr);
}
#else
static void asan_before_jump(void**, const void*, size_t) {}
static void asan_after_jump(void*) {}
#endif

// ---------------- TaskGroup ----------------

TaskGroup::TaskGroup(TaskControl* control, int index)
    : control_(control), index_(index), steal_seed_(fast_rand() | 1) {
    CHECK_EQ(rq_.init(1024), 0);
}

void TaskGroup::run_main_task() {
    tls_task_group = this;
    {
        pthread_attr_t attr;
        if (pthread_getattr_np(pthread_self(), &attr) == 0) {
            void* base = nullptr;
            size_t size = 0;
            pthread_attr_getstack(&attr, &base, &size);
            worker_stack_base_ = base;
            worker_stack_size_ = size;
            pthread_attr_destroy(&attr);
        }
    }
    while (true) {
        TaskMeta* m = wait_task();
        if (m == nullptr) break;  // stopped
        sched_to(m);
        // Back on the main context: first run the publish-after-switch
        // hook of the fiber that just switched out (butex parking, yield
        // requeue) — it must run before we pick another task.
        if (remained_fn_ != nullptr) {
            void (*fn)(void*) = remained_fn_;
            void* arg = remained_arg_;
            remained_fn_ = nullptr;
            remained_arg_ = nullptr;
            fn(arg);
        }
        if (cur_ended_) {
            // The fiber finished: recycle stack + slot, wake joiners.
            TaskMeta* dead = cur_meta_;
            cur_meta_ = nullptr;
            cur_ended_ = false;
            return_stack(&dead->stack);
            std::atomic<int>* vb = butex_word(dead->version_butex);
            const fiber_t dead_tid = dead->tid;
            vb->fetch_add(1, std::memory_order_release);
            butex_wake_all(dead->version_butex);
            control_->nfibers.fetch_sub(1, std::memory_order_relaxed);
            return_resource<TaskMeta>((ResourceId)((dead_tid & 0xffffffff) - 1));
        } else {
            cur_meta_ = nullptr;
        }
    }
}

TaskMeta* TaskGroup::wait_task() {
    // The park's 100 ms timeout is a safety net: when it, and no signal,
    // is what ended the park and a runnable fiber is then found, that
    // fiber's wake-up was lost (or went to a worker that lost the race
    // for it, a window of microseconds in 100 ms).
    bool park_timed_out = false;
    while (true) {
        // Urgent handoff runs before any queue: run_urgent parked its
        // caller with `next_meta_` armed; the requeue hook has already
        // republished the caller by the time we get here.
        if (next_meta_ != nullptr) {
            TaskMeta* m = next_meta_;
            next_meta_ = nullptr;
            return m;
        }
        if (control_->stopped()) return nullptr;
        TaskMeta* m = nullptr;
        if (rq_.pop(&m) || control_->pop_remote(&m) ||
            control_->steal_task(&m, &steal_seed_, index_)) {
            if (park_timed_out) *g_park_timeouts_found_work << 1;
            return m;
        }
        const ParkingLot::State st = control_->parking_lot().get_state();
        // Re-check after reading the state so a concurrent signal is never
        // missed (the futex value would have changed).
        if (rq_.pop(&m) || control_->pop_remote(&m) ||
            control_->steal_task(&m, &steal_seed_, index_)) {
            if (park_timed_out) *g_park_timeouts_found_work << 1;
            return m;
        }
        park_timed_out = control_->parking_lot().wait(st);
        if (park_timed_out) *g_park_timeouts << 1;
    }
}

void TaskGroup::sched_to(TaskMeta* next) {
    if (next->ready_us != 0) {
        stage::Add(stage::kWakeToRun, stage::now_us() - next->ready_us);
        next->ready_us = 0;
    }
    cur_meta_ = next;
    cur_ended_ = false;
    asan_before_jump(&worker_asan_fake_, next->stack.base,
                     next->stack.size);
    tf_jump_fcontext(&main_ctx_, next->stack.context, next);
    asan_after_jump(worker_asan_fake_);
}

void TaskGroup::fiber_entry(void* arg) {
    TaskMeta* m = (TaskMeta*)arg;
    asan_after_jump(m->asan_fake);
    m->ret = m->fn(m->arg);
    // Fiber-local storage: run dtors + recycle the keytable (reference
    // key.cpp return_keytable at task_runner end).
    if (m->local_storage != nullptr) {
        fiber_internal::return_keytable(m->local_storage);
        m->local_storage = nullptr;
    }
    TaskGroup::tls_group()->exit_current();
}

void TaskGroup::exit_current() {
    cur_ended_ = true;
    TaskMeta* m = cur_meta_;
    // null save: the fiber context dies here; ASan frees its fake frames.
    asan_before_jump(nullptr, worker_stack_base_, worker_stack_size_);
    tf_jump_fcontext(&m->stack.context, main_ctx_, nullptr);
    CHECK(false) << "dead fiber resumed";
}

// errno is thread-local, but a parked fiber can resume on a DIFFERENT
// worker — and the compiler may legally CSE __errno_location() (declared
// const) across the context switch, reading/writing the OLD worker's
// errno after resume. Make errno effectively fiber-local by saving it
// around the switch (reference task_group.cpp:711-712,794-795 "Save errno
// so that errno is bthread-specific"), through noinline helpers so the
// location is recomputed on the resuming thread.
__attribute__((noinline)) static int read_errno_here() { return errno; }
__attribute__((noinline)) static void write_errno_here(int v) { errno = v; }

void TaskGroup::sched_park() {
    TaskMeta* m = cur_meta_;
    // A parked fiber may resume on a DIFFERENT pthread: flush + detach
    // the thread-local batching scopes (park hooks first — the write-
    // coalescing flush may spawn fibers whose wake signals then ride the
    // batcher's own flush). Without this, a mid-round park would strand
    // deferred work on the old thread and dangle its thread-local
    // pointers.
    run_park_hooks();
    WakeBatcher::FlushCurrent();
    flight::Record(flight::kSchedPark, (uint64_t)m->tid, 0);
    const int saved_errno = read_errno_here();
    asan_before_jump(&m->asan_fake, worker_stack_base_,
                     worker_stack_size_);
    tf_jump_fcontext(&m->stack.context, main_ctx_, nullptr);
    // Resumed later on possibly a DIFFERENT worker; re-read tls_group —
    // callers must not cache `this` across sched_park (they don't: all
    // callers go through TaskGroup::tls_group()). `m` lives on this fiber
    // stack and is still our own meta.
    asan_after_jump(m->asan_fake);
    write_errno_here(saved_errno);
}

// ---------------- park hooks + wake batching (ISSUE 7) ----------------

namespace {
constexpr int kMaxParkHooks = 4;
std::atomic<void (*)()> g_park_hooks[kMaxParkHooks];
std::atomic<int> g_npark_hooks{0};

thread_local WakeBatcher* g_wake_batcher = nullptr;
}  // namespace

void register_park_hook(void (*fn)()) {
    const int n = g_npark_hooks.load(std::memory_order_acquire);
    for (int i = 0; i < n; ++i) {
        if (g_park_hooks[i].load(std::memory_order_relaxed) == fn) return;
    }
    static std::mutex* mu = new std::mutex;
    std::lock_guard<std::mutex> g(*mu);
    const int cur = g_npark_hooks.load(std::memory_order_relaxed);
    for (int i = 0; i < cur; ++i) {
        if (g_park_hooks[i].load(std::memory_order_relaxed) == fn) return;
    }
    CHECK_LT(cur, kMaxParkHooks) << "too many park hooks";
    g_park_hooks[cur].store(fn, std::memory_order_relaxed);
    g_npark_hooks.store(cur + 1, std::memory_order_release);
}

void run_park_hooks() {
    const int n = g_npark_hooks.load(std::memory_order_acquire);
    for (int i = 0; i < n; ++i) {
        g_park_hooks[i].load(std::memory_order_relaxed)();
    }
}

WakeBatcher::WakeBatcher() {
    if (g_wake_batcher == nullptr) {
        g_wake_batcher = this;
        armed_ = true;
    }
}

WakeBatcher::~WakeBatcher() {
    if (!armed_) return;
    Flush();
    if (g_wake_batcher == this) g_wake_batcher = nullptr;
}

void WakeBatcher::Flush() {
    for (int i = 0; i < npools_; ++i) {
        pools_[i]->parking_lot().signal(counts_[i]);
    }
    npools_ = 0;
}

bool WakeBatcher::TryBatch(TaskControl* c, int n) {
    WakeBatcher* b = g_wake_batcher;
    if (b == nullptr) return false;
    for (int i = 0; i < b->npools_; ++i) {
        if (b->pools_[i] == c) {
            b->counts_[i] += n;
            return true;
        }
    }
    if (b->npools_ >= kMaxPools) return false;
    b->pools_[b->npools_] = c;
    b->counts_[b->npools_] = n;
    ++b->npools_;
    return true;
}

void WakeBatcher::FlushCurrent() {
    WakeBatcher* b = g_wake_batcher;
    if (b == nullptr) return;
    b->Flush();
    b->armed_ = false;
    g_wake_batcher = nullptr;
}

namespace {
void requeue_meta_cb(void* arg) {
    fiber_requeue_meta((TaskMeta*)arg);
}
}  // namespace

void TaskGroup::yield() {
    TaskMeta* m = cur_meta_;
    set_remained(requeue_meta_cb, m);
    sched_park();
}

void TaskGroup::ready_to_run(TaskMeta* m) {
    if (!rq_.push(m)) {
        control_->ready_to_run_remote(m);
        return;
    }
    // Run-queue depth high-water: a sustained climb means admission
    // outruns dispatch (the ROADMAP item-4 signature). One relaxed load
    // + compare in the common (not-a-new-max) case.
    if (control_->rq_highwater_cell_ != nullptr) {
        control_->rq_highwater_cell_->update_max(
            (int64_t)rq_.volatile_size());
    }
    if (!WakeBatcher::TryBatch(control_, 1)) {
        control_->parking_lot().signal(1);
    }
}

void TaskGroup::run_urgent(TaskMeta* m) {
    TaskMeta* self = cur_meta_;
    next_meta_ = m;
    if (control_->urgent_cell_ != nullptr) control_->urgent_cell_->add(1);
    set_remained(requeue_meta_cb, self);
    sched_park();
}

// ---------------- TaskControl ----------------

TaskControl::TaskControl() {
    CHECK_EQ(remote_ring_.init(4096), 0);
}

TaskControl* TaskControl::singleton() {
    static TaskControl* c = new TaskControl;
    return c;
}

// Tags are bounded (reference validates against task_group_ntags the
// same way): each pool is 2+ permanent pthreads, so an unvalidated
// dynamic tag would leak threads without bound. Lock-free fast path via
// a fixed atomic array — spawns on hot tagged pools must not contend on
// a registry mutex.
static constexpr int kMaxWorkerTag = 64;
static std::atomic<TaskControl*> g_tag_pools[kMaxWorkerTag];

TaskControl* TaskControl::of_tag(int tag) {
    if (tag <= 0) {
        LOG_IF(ERROR, tag < 0) << "invalid worker tag " << tag
                               << "; using the default pool";
        return singleton();
    }
    if (tag >= kMaxWorkerTag) {
        LOG(ERROR) << "worker tag " << tag << " out of range (max "
                   << kMaxWorkerTag - 1 << "); using the default pool";
        return singleton();
    }
    TaskControl* c = g_tag_pools[tag].load(std::memory_order_acquire);
    if (c != nullptr) return c;
    static std::mutex* mu = new std::mutex;
    std::lock_guard<std::mutex> g(*mu);
    c = g_tag_pools[tag].load(std::memory_order_relaxed);
    if (c != nullptr) return c;
    c = new TaskControl;
    c->tag_ = tag;
    g_tag_pools[tag].store(c, std::memory_order_release);
    return c;
}

void TaskControl::ForEachPool(void (*fn)(int tag, TaskControl* c,
                                         void* arg),
                              void* arg) {
    fn(0, singleton(), arg);
    for (int t = 1; t < kMaxWorkerTag; ++t) {
        TaskControl* c = g_tag_pools[t].load(std::memory_order_acquire);
        if (c != nullptr) fn(t, c, arg);
    }
}

void TaskControl::ensure_started() {
    if (started_.load(std::memory_order_acquire)) return;
    std::lock_guard<std::mutex> g(start_mu_);
    if (started_.load(std::memory_order_relaxed)) return;
    int concurrency;
    if (tag_ != 0) {
        concurrency = std::max(1, FLAGS_fiber_tagged_worker_count.get());
    } else {
        concurrency = FLAGS_fiber_worker_count.get();
        if (concurrency <= 0) {
            const unsigned hc = std::thread::hardware_concurrency();
            concurrency = (int)std::max(4u, hc + 1);
        }
    }
    // Telemetry cells before the first worker runs: the hot paths
    // null-check but never lock the family mutex.
    const std::string pool = std::to_string(tag_);
    steals_cell_ = sched_steals()->get_stats({pool});
    remote_overflow_cell_ = sched_remote_overflows()->get_stats({pool});
    urgent_cell_ = sched_urgent()->get_stats({pool});
    rq_highwater_cell_ = sched_rq_highwater()->get_stats({pool});
    *g_park_timeouts << 0;  // on /vars from the first scrape
    *g_park_timeouts_found_work << 0;
    add_workers_locked(concurrency);
    started_.store(true, std::memory_order_release);
}

int64_t TaskControl::steals() const {
    return steals_cell_ != nullptr ? steals_cell_->get() : 0;
}
int64_t TaskControl::remote_overflows() const {
    return remote_overflow_cell_ != nullptr ? remote_overflow_cell_->get()
                                            : 0;
}
int64_t TaskControl::urgent_handoffs() const {
    return urgent_cell_ != nullptr ? urgent_cell_->get() : 0;
}
int64_t TaskControl::runqueue_highwater() const {
    return rq_highwater_cell_ != nullptr ? rq_highwater_cell_->get() : 0;
}
void TaskControl::reset_runqueue_highwater() {
    if (rq_highwater_cell_ != nullptr) rq_highwater_cell_->set(0);
}

void TaskControl::add_workers_locked(int n) {
    if (stopped_.load(std::memory_order_relaxed)) return;
    for (int i = 0; i < n; ++i) {
        const size_t idx = ngroup_.load(std::memory_order_relaxed);
        if (idx >= kMaxGroups) {
            LOG(ERROR) << "worker pool is at its " << kMaxGroups
                       << "-group capacity";
            return;
        }
        TaskGroup* tg = new TaskGroup(this, (int)idx);
        groups_[idx] = tg;
        // Publish before the worker runs (steal_task scans [0, ngroup)).
        ngroup_.store(idx + 1, std::memory_order_release);
        workers_.emplace_back([tg] { tg->run_main_task(); });
    }
}

void TaskControl::set_concurrency(int n) {
    std::lock_guard<std::mutex> g(start_mu_);
    if (!started_.load(std::memory_order_relaxed)) {
        FLAGS_fiber_worker_count.set(n);
        return;
    }
    // Live growth (reference TaskControl::add_workers): a long-running
    // server can scale its pool up; shrinking is not supported.
    const int cur = (int)ngroup_.load(std::memory_order_relaxed);
    if (n > cur) add_workers_locked(n - cur);
}

void TaskControl::ready_to_run(TaskMeta* m) {
    m->ready_us = stage::now_us();
    TaskGroup* g = tls_task_group;
    // The local-queue shortcut is only valid on a worker of THIS pool: a
    // tagged fiber woken from another pool's worker (or a plain pthread)
    // must go through the remote queue of its own pool.
    if (g != nullptr && g->control() == this) {
        g->ready_to_run(m);
    } else {
        ready_to_run_remote(m);
    }
}

void TaskControl::ready_to_run_remote(TaskMeta* m) {
    if (!remote_ring_.push(m)) {
        // Ring full: spill to the mutexed overflow list rather than
        // spinning — fiber spawns must never be dropped or block.
        {
            std::lock_guard<std::mutex> g(overflow_mu_);
            overflow_q_.push_back(m);
            overflow_size_.fetch_add(1, std::memory_order_release);
        }
        if (remote_overflow_cell_ != nullptr) {
            remote_overflow_cell_->add(1);
        }
    }
    if (!WakeBatcher::TryBatch(this, 1)) {
        parking_lot_.signal(1);
    }
}

bool TaskControl::pop_remote(TaskMeta** m) {
    // Ring first: ring entries are OLDER than anything spilled (spills
    // only happen when the ring is full). To keep the spill from
    // starving while the ring stays busy, each successful pop migrates a
    // bounded batch of spilled fibers into the freed ring slots — they
    // land BEHIND the remaining ring entries, preserving rough FIFO,
    // and both queues make progress under sustained saturation.
    if (remote_ring_.pop(m)) {
        if (overflow_size_.load(std::memory_order_acquire) != 0) {
            std::lock_guard<std::mutex> g(overflow_mu_);
            for (int i = 0; i < 64 && !overflow_q_.empty(); ++i) {
                if (!remote_ring_.push(overflow_q_.front())) break;
                overflow_q_.pop_front();
                overflow_size_.fetch_sub(1, std::memory_order_release);
            }
        }
        return true;
    }
    if (overflow_size_.load(std::memory_order_acquire) == 0) return false;
    std::lock_guard<std::mutex> g(overflow_mu_);
    if (overflow_q_.empty()) return false;
    *m = overflow_q_.front();
    overflow_q_.pop_front();
    overflow_size_.fetch_sub(1, std::memory_order_release);
    return true;
}

bool TaskControl::steal_task(TaskMeta** m, uint64_t* seed, int exclude) {
    const size_t n = ngroup_.load(std::memory_order_acquire);
    if (n <= 1) return false;
    // xorshift over group indices, starting at a pseudo-random offset.
    uint64_t s = *seed;
    s ^= s << 13;
    s ^= s >> 7;
    s ^= s << 17;
    *seed = s;
    const size_t start = (size_t)(s % n);
    for (size_t i = 0; i < n; ++i) {
        const size_t idx = (start + i) % n;
        if ((int)idx == exclude) continue;
        if (groups_[idx]->steal(m)) {
            if (steals_cell_ != nullptr) steals_cell_->add(1);
            return true;
        }
    }
    return false;
}

void TaskControl::stop_and_join() {
    // Snapshot the workers under start_mu_ (serializing against
    // set_concurrency growth), but JOIN outside it: a fiber on a worker
    // may itself be blocked in set_concurrency on start_mu_, and joining
    // that worker while holding the lock would deadlock. Once stopped_
    // is set, add_workers_locked refuses to grow, so the snapshot is
    // complete.
    std::vector<std::thread> to_join;
    {
        std::lock_guard<std::mutex> g(start_mu_);
        stopped_.store(true, std::memory_order_release);
        parking_lot_.stop();
        to_join = std::move(workers_);
        workers_.clear();
    }
    for (auto& t : to_join) {
        if (t.joinable()) t.join();
    }
}

// ---------------- fiber API ----------------

TaskMeta* fiber_meta_of(fiber_t tid) {
    if (tid == INVALID_FIBER) return nullptr;
    const ResourceId slot = (ResourceId)((tid & 0xffffffff) - 1);
    TaskMeta* m = address_resource<TaskMeta>(slot);
    if (m == nullptr || m->version_butex == nullptr) return nullptr;
    const uint32_t expect_version = (uint32_t)(tid >> 32);
    if ((uint32_t)butex_word(m->version_butex)
            ->load(std::memory_order_acquire) != expect_version) {
        return nullptr;
    }
    return m;
}

void fiber_requeue_meta(TaskMeta* m) {
    (m->control != nullptr ? m->control : TaskControl::singleton())
        ->ready_to_run(m);
}

void fiber_requeue(fiber_t tid) {
    TaskMeta* m = fiber_meta_of(tid);
    if (m != nullptr) fiber_requeue_meta(m);
}

static int start_fiber_impl(fiber_t* tid, const FiberAttr* attr,
                            void* (*fn)(void*), void* arg,
                            bool urgent = false) {
    TaskControl* c = TaskControl::of_tag(attr != nullptr ? attr->tag : 0);
    c->ensure_started();
    ResourceId slot;
    TaskMeta* m = get_resource<TaskMeta>(&slot);
    if (m == nullptr) return -1;
    if (m->version_butex == nullptr) {
        m->version_butex = butex_create();
    }
    m->version =
        (uint32_t)butex_word(m->version_butex)->load(std::memory_order_relaxed);
    m->fn = fn;
    m->arg = arg;
    m->ret = nullptr;
    m->local_storage = nullptr;  // fresh fiber: no inherited fiber-locals
    // Stale handle from the slot's previous tenant would hand ASan a freed
    // fake stack on this fiber's first switch-in.
    m->asan_fake = nullptr;
    m->ready_us = 0;
    m->stack_type = attr ? attr->stack_type : STACK_TYPE_NORMAL;
    m->control = c;
    m->tid = ((fiber_t)m->version << 32) | (fiber_t)(slot + 1);
    if (!get_stack(&m->stack, m->stack_type, TaskGroup::fiber_entry)) {
        return_resource<TaskMeta>(slot);
        return -1;
    }
    if (tid) *tid = m->tid;
    c->nfibers.fetch_add(1, std::memory_order_relaxed);
    TaskGroup* g = tls_task_group;
    if (urgent && g != nullptr && g->current() != nullptr &&
        g->control() == c) {
        g->run_urgent(m);  // runs m NOW; caller resumes via the queues
    } else {
        c->ready_to_run(m);
    }
    return 0;
}

int fiber_start_background(fiber_t* tid, const FiberAttr* attr,
                           void* (*fn)(void*), void* arg) {
    return start_fiber_impl(tid, attr, fn, arg);
}

int fiber_start_urgent(fiber_t* tid, const FiberAttr* attr, void* (*fn)(void*),
                       void* arg) {
    // Run-new-fiber-immediately (reference task_group.cpp
    // start_foreground → sched_to): the new fiber takes this worker right
    // away and the caller is requeued — the core latency trick for
    // dispatching a just-parsed request before the parser fiber resumes.
    return start_fiber_impl(tid, attr, fn, arg, /*urgent=*/true);
}

int fiber_join(fiber_t tid, void** ret) {
    if (ret) *ret = nullptr;
    if (tid == INVALID_FIBER) return 0;
    if (tid == fiber_self()) return EINVAL;  // self-join would park forever
    const ResourceId slot = (ResourceId)((tid & 0xffffffff) - 1);
    TaskMeta* m = address_resource<TaskMeta>(slot);
    if (m == nullptr || m->version_butex == nullptr) return 0;
    const uint32_t expect_version = (uint32_t)(tid >> 32);
    std::atomic<int>* word = butex_word(m->version_butex);
    while ((uint32_t)word->load(std::memory_order_acquire) == expect_version) {
        butex_wait(m->version_butex, (int)expect_version, nullptr);
    }
    return 0;
}

bool fiber_exists(fiber_t tid) { return fiber_meta_of(tid) != nullptr; }

fiber_t fiber_self() {
    TaskGroup* g = tls_task_group;
    if (g == nullptr || g->current() == nullptr) return INVALID_FIBER;
    return g->current()->tid;
}

void fiber_yield() {
    TaskGroup* g = tls_task_group;
    if (g == nullptr || g->current() == nullptr) {
        std::this_thread::yield();
        return;
    }
    g->yield();
}

namespace {
void usleep_timer_cb(void* arg) { fiber_requeue((fiber_t)(uintptr_t)arg); }

struct SleepArgs {
    fiber_t tid;
    int64_t abstime;
};

void usleep_remained_cb(void* raw) {
    SleepArgs* sa = (SleepArgs*)raw;  // lives on the parked fiber's stack
    TimerThread::singleton()->schedule(usleep_timer_cb,
                                       (void*)(uintptr_t)sa->tid, sa->abstime);
}
}  // namespace

int fiber_usleep(int64_t us) {
    TaskGroup* g = tls_task_group;
    if (g == nullptr || g->current() == nullptr) {
        ::usleep((useconds_t)us);
        return 0;
    }
    TaskMeta* m = g->current();
    SleepArgs sa{m->tid, monotonic_time_us() + us};
    g->set_remained(usleep_remained_cb, &sa);
    g->sched_park();
    return 0;
}

void fiber_set_worker_count(int n) {
    TaskControl::singleton()->set_concurrency(n);
}
int fiber_get_worker_count() {
    return TaskControl::singleton()->concurrency();
}

}  // namespace tpurpc
