// ParkingLot: where idle workers sleep and task submitters wake them.
// Modeled on reference src/bthread/parking_lot.h — a futex word whose value
// changes on every signal, so a worker that re-checks queues between
// reading the word and parking can never miss a wakeup.
#pragma once

#include "tfiber/sys_futex.h"

namespace tpurpc {

class ParkingLot {
public:
    struct State {
        int val;
    };

    // Read current state; pass to wait() so an intervening signal aborts
    // the park.
    State get_state() {
        return State{pending_signal_.load(std::memory_order_acquire)};
    }

    void signal(int num_task) {
        pending_signal_.fetch_add((num_task << 1), std::memory_order_release);
        futex_wake_private(&pending_signal_, num_task);
    }

    // Park until signalled (or 100ms safety timeout). True when it was the
    // timeout that ended the park: the caller counts those, and whether a
    // runnable fiber was waiting behind one (a wake-up that never came).
    bool wait(const State& expected) {
        timespec ts{0, 100 * 1000 * 1000};
        return futex_wait_private(&pending_signal_, expected.val, &ts) != 0 &&
               errno == ETIMEDOUT;
    }

    void stop() {
        pending_signal_.fetch_or(1, std::memory_order_release);
        futex_wake_private(&pending_signal_, 1 << 30);
    }

private:
    // Bit 0: stopped flag; upper bits: signal counter.
    std::atomic<int> pending_signal_{0};
};

}  // namespace tpurpc
