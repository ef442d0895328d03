// TaskMeta: the fiber descriptor, pooled in a ResourcePool and addressed by
// fiber_t = (version<<32)|slot. Modeled on reference src/bthread/task_meta.h.
#pragma once

#include <atomic>
#include <cstdint>
#include <functional>

#include "tfiber/fiber.h"
#include "tfiber/stack.h"

namespace tpurpc {

class TaskControl;
class TaskGroup;

struct TaskMeta {
    // Entry + result.
    void* (*fn)(void*) = nullptr;
    void* arg = nullptr;
    void* ret = nullptr;

    // Join/versioning: `version_butex` points to a pooled butex word whose
    // value is the current version of this slot. fiber_join waits for it to
    // move past the version embedded in the tid (reference task_meta.h
    // version_butex; controller retries rely on the same scheme for ids).
    uint32_t version = 0;
    void* version_butex = nullptr;

    StackStorage stack;
    int stack_type = STACK_TYPE_NORMAL;
    fiber_t tid = INVALID_FIBER;

    // Fiber-local storage (lazily created; reference bthread keytables).
    void* local_storage = nullptr;

    // The worker pool this fiber belongs to (tag routing: a parked fiber
    // must requeue to ITS pool, and cross-pool wakeups must not land on
    // the waker's local queue).
    TaskControl* control = nullptr;

    bool about_to_quit = false;

    // Stage clock (tvar/stage_recorder.h): when this fiber was last made
    // runnable (TaskControl::ready_to_run); sched_to turns it into one
    // tfiber.wake_to_run sample and clears it. 0 = no stamp (an urgent
    // start runs at once and is not stamped).
    int64_t ready_us = 0;

    // ASan fake-stack handle saved when this fiber switches out (fiber
    // annotations in task_group.cc; unused in non-ASan builds).
    void* asan_fake = nullptr;
};

}  // namespace tpurpc
