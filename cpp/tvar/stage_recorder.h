// StageRecorder: the one stage clock of the served path.
//
// A process-wide table of named stages. A request (or a parked fiber, or
// a link descriptor) carries its last stamp with it; each seam does
//     now = stage::now_us(); stage::Add(kStage, now - last); last = now;
// so one clock read per seam feeds the stage histogram, the rpcz phase
// set at that seam and anything else that wants the time there.
//
// Every stage is CUMULATIVE: count / sum_us / max_us and a 256-bucket
// histogram (the PercentileHistogram layout) that are never windowed and
// never reset, so a reader that scrapes twice gets the interval between
// the scrapes exactly as `after - before` (benchmark/stages.py does;
// LatencyRecorder's sliding window cannot). Always on, like /loops: no
// flag. A write touches only this thread's cell of the stage (allocated
// on the thread's first sample; single writer, plain relaxed stores);
// reads combine all threads' cells plus what exited threads folded in.
//
// Published from this one table: /status?format=json "stages", the text
// /status table, the prometheus histogram family rpc_stage_us{stage=...}
// on /metrics, and the C API tpurpc_stage_dump for processes without a
// portal.
#pragma once

#include <cstdint>
#include <string>
#include <vector>

#include "tbase/time.h"
#include "tvar/percentile.h"

namespace tpurpc {
namespace stage {

// The served path's stages, named <layer>.<stage> after the layer that
// owns the wait (PERF.md section 3 lists each beside the metric that
// reads it). The table is this enum: a new stage is a new line here and
// its name in stage_recorder.cc. Readers go by name.
enum Id : int {
    kLinkHandoff = 0,     // tici.link_handoff
    kConsumeToCut,        // tnet.consume_to_cut
    kDispatchToHandler,   // tfiber.dispatch_to_handler
    kHandler,             // trpc.handler
    kRespond,             // trpc.respond
    kWriteQueue,          // tnet.write_queue
    kIssue,               // trpc.issue (client)
    kMatch,               // trpc.match (client)
    kCallerWake,          // trpc.caller_wake (client, synchronous calls)
    kWakeToRun,           // tfiber.wake_to_run
    kTakeWait,            // tdev.take_wait (inside trpc.handler)
    kDevReply,            // tdev.reply (ends past trpc.handler's end)
    kPublished,           // the dumps show the stages above
    kTestOnly = kPublished,  // unit tests sample this one; never shown
    kCount,
};

// The stage clock: CLOCK_MONOTONIC in microseconds, the clock the rpcz
// phases and every deadline already use (same host => same clock in both
// processes of a shm link).
inline int64_t now_us() { return monotonic_time_us(); }

// Low 32 bits of a stamp, for the 4 spare bytes of a link descriptor;
// Elapsed32 is the difference modulo 2^32 us (wraps after ~71 min).
inline uint32_t Low32(int64_t us) { return (uint32_t)(uint64_t)us; }
inline int64_t Elapsed32(int64_t now_us, uint32_t then_low32) {
    return (int64_t)(uint32_t)(Low32(now_us) - then_low32);
}

// One sample. Negative durations count as 0. Out of line on purpose: the
// thread-local cell is looked up inside, never cached by a caller across
// a fiber switch.
void Add(int stage, int64_t us);

struct Snapshot {
    std::string name;
    uint64_t count = 0;
    int64_t sum_us = 0;
    int64_t max_us = 0;
    HistogramSnapshot hist;
};

// One stage, combined over all threads.
Snapshot SnapshotOf(int stage);
// Every published stage, in index order.
std::vector<Snapshot> SnapshotAll();

// {"<stage>":{"count":N,"sum_us":N,"max_us":N,"buckets":[[index,count],
// ...non-zero only]},...} -- the object /status?format=json embeds under
// "stages" and tpurpc_stage_dump returns.
std::string DumpJson();
// mean / p50 / p99 / max since start, one line a stage.
std::string DumpText();
// One histogram family rpc_stage_us{stage="..."} (le at octave bounds).
void DumpPrometheus(std::string* out);

}  // namespace stage
}  // namespace tpurpc
