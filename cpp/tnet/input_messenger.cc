#include "tnet/input_messenger.h"

#include <sys/socket.h>

#include <algorithm>
#include <cerrno>

#include "tbase/errno.h"
#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/logging.h"
#include "tfiber/fiber.h"
#include "tfiber/task_group.h"
#include "tnet/fault_injection.h"
#include "tnet/transport.h"
#include "tvar/reducer.h"
#include "tvar/stage_recorder.h"

// Run-to-completion dispatch (ISSUE 7): up to this many small messages
// per readiness burst process ON the input fiber (no spawn, no switch);
// the rest fan out to fibers as before, so a huge burst still uses every
// core. 0 disables inlining entirely.
DEFINE_int32(inline_dispatch_budget, 64,
             "inline-safe messages processed on the input fiber per "
             "readiness burst before fanning out; 0 disables");
DEFINE_int32(inline_dispatch_max_bytes, 16384,
             "largest message (header+body) eligible for inline dispatch");

namespace tpurpc {

// ---------------- inline dispatch budget ----------------

namespace inline_dispatch {

namespace {
// Armed/spent budget of the current thread's messenger round. Reset on
// fiber park via the task_group park hook (the resumed fiber may be on
// another thread; its round is conservatively over).
thread_local int g_budget = 0;
thread_local bool g_armed = false;
// True only while a message that Acquire() admitted is being processed
// inline — the Refund() guard: fan-out paths (pending chain, process
// fibers) also reach the RPC layer, but never through an Acquire, and
// must not give back budget they never took.
thread_local bool g_acquired_current = false;

LazyAdder* dispatches_adder() {
    static auto* a = new LazyAdder("rpc_dispatcher_inline_dispatches");
    return a;
}
LazyAdder* overflows_adder() {
    static auto* a = new LazyAdder("rpc_dispatcher_inline_overflows");
    return a;
}
LazyAdder* handler_adder() {
    static auto* a = new LazyAdder("rpc_server_inline_handlers");
    return a;
}
LazyAdder* desc_exempt_adder() {
    static auto* a =
        new LazyAdder("rpc_dispatcher_descriptor_exempt_bytes");
    return a;
}

void ResetOnPark() {
    g_budget = 0;
    g_armed = false;
    g_acquired_current = false;
}

void ArmRound() {
    static const bool hook_registered = [] {
        register_park_hook(&ResetOnPark);
        return true;
    }();
    (void)hook_registered;
    g_budget = FLAGS_inline_dispatch_budget.get();
    g_armed = g_budget > 0;
}

void DisarmRound() {
    g_budget = 0;
    g_armed = false;
    g_acquired_current = false;
}

void EndInlineProcess() { g_acquired_current = false; }
}  // namespace

bool RoundArmed() { return g_armed; }

bool Acquire(size_t nbytes) {
    if (!g_armed || nbytes == 0 ||
        nbytes > (size_t)FLAGS_inline_dispatch_max_bytes.get()) {
        return false;
    }
    if (g_budget <= 0) {
        **overflows_adder() << 1;
        return false;
    }
    --g_budget;
    g_acquired_current = true;
    **dispatches_adder() << 1;
    flight::Record(flight::kSchedInline, nbytes, 0);
    return true;
}

void Refund() {
    // Only a message Acquire() admitted may give its unit back — and it
    // did NOT run to completion after all (the layer above fanned it
    // out), so take back Acquire's count too: inline_dispatches reports
    // actual run-to-completion messages.
    if (g_armed && g_acquired_current) {
        ++g_budget;
        g_acquired_current = false;
        **dispatches_adder() << -1;
    }
}

int64_t dispatches() { return (**dispatches_adder()).get_value(); }
int64_t overflows() { return (**overflows_adder()).get_value(); }
int64_t handler_inlines() { return (**handler_adder()).get_value(); }
void CountHandlerInline() { **handler_adder() << 1; }
void ExemptDescriptorBytes(size_t nbytes) {
    **desc_exempt_adder() << (int64_t)nbytes;
}
int64_t descriptor_exempt_bytes() {
    return (**desc_exempt_adder()).get_value();
}

}  // namespace inline_dispatch

namespace {

constexpr size_t kReadBurst = 512 * 1024;

// Chaos seam for the plain-fd read path (transports consult the
// injection layer inside their own Pump implementations). Same contract
// as append_from_file_descriptor: >0 bytes made progress, 0 EOF, -1 with
// errno (EAGAIN = drained).
ssize_t ChaosReadFromFd(Socket* s) {
    const FaultAction fa =
        FaultInjection::Decide(FaultOp::kRead, s->remote_side(), kReadBurst);
    switch (fa.kind) {
        case FaultAction::kReset:
            errno = ECONNRESET;
            return -1;
        case FaultAction::kDelay:
            fiber_usleep(fa.delay_us);
            break;
        case FaultAction::kShort:
            return s->read_buf.append_from_file_descriptor(
                s->fd(), std::max<size_t>(1, fa.max_bytes));
        case FaultAction::kDrop: {
            // Read and discard: bytes vanish from the stream (the peer
            // believes they arrived). Returning r > 0 with nothing
            // appended just reports progress to the caller's loop.
            char tmp[4096];
            const ssize_t r = recv(s->fd(), tmp, sizeof(tmp), 0);
            return r;
        }
        case FaultAction::kCorrupt: {
            char tmp[4096];
            const ssize_t r = recv(s->fd(), tmp, sizeof(tmp), 0);
            if (r <= 0) return r;
            tmp[fa.aux % (uint64_t)r] ^= 0x20;
            s->read_buf.append(tmp, (size_t)r);
            return r;
        }
        default:
            break;
    }
    return s->read_buf.append_from_file_descriptor(s->fd(), kReadBurst);
}

struct ProcessArgs {
    InputMessageBase* msg;
    const Protocol* proto;
};

void* process_msg_thunk(void* arg) {
    ProcessArgs* pa = (ProcessArgs*)arg;
    pa->proto->process(pa->msg);
    delete pa;
    return nullptr;
}

// Cut one message. Returns OK/NOT_ENOUGH_DATA/ERROR (TRY_OTHERS resolved
// internally by iterating the messenger's protocol set).
ParseResult CutInputMessage(Socket* s, const std::vector<int>& protocols,
                            bool read_eof) {
    // Preferred protocol first (sniffed once per connection, reference
    // input_messenger.cpp:84).
    if (s->preferred_protocol_index >= 0) {
        const Protocol* p = GetProtocol(s->preferred_protocol_index);
        // Zero-cut fast path (ISSUE 7): peek the fixed header from
        // contiguous bytes, learn the full frame size ONCE, then skip
        // parse entirely until the frame is complete — a large message
        // arriving in many reads costs one peek instead of a cut/re-parse
        // per read.
        if (p->peek != nullptr) {
            if (s->pending_frame_bytes == 0) {
                if (s->read_buf.size() < p->peek_len) {
                    // Split header: wait (only sticky sockets take this
                    // path, so the bytes can only be this protocol's).
                    return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
                }
                char aux[64];
                CHECK_LE(p->peek_len, sizeof(aux));
                const char* hdr =
                    (const char*)s->read_buf.fetch(aux, p->peek_len);
                const int64_t total = p->peek(hdr, s);
                if (total < 0) {
                    return ParseResult::make(ParseError::ERROR);
                }
                if (total == 0) {
                    // Not this protocol after all: drop stickiness and
                    // re-sniff below (the TRY_OTHERS contract).
                    s->preferred_protocol_index = -1;
                } else {
                    s->pending_frame_bytes = total;
                }
            }
            if (s->pending_frame_bytes > 0) {
                if (s->read_buf.size() < (size_t)s->pending_frame_bytes) {
                    return ParseResult::make(ParseError::NOT_ENOUGH_DATA);
                }
                s->pending_frame_bytes = 0;
                ParseResult r =
                    p->parse(&s->read_buf, s, read_eof, p->parse_arg);
                if (r.error == ParseError::OK) {
                    r.msg->protocol_index = s->preferred_protocol_index;
                    return r;
                }
                if (r.error == ParseError::ERROR) return r;
                // A complete peeked frame the parser then refused:
                // inconsistent parser state — drop stickiness and
                // re-sniff (defensive; peek and parse agree by
                // construction).
                s->preferred_protocol_index = -1;
            }
        } else {
            ParseResult r = p->parse(&s->read_buf, s, read_eof, p->parse_arg);
            if (r.error != ParseError::TRY_OTHERS) {
                if (r.error == ParseError::OK) {
                    r.msg->protocol_index = s->preferred_protocol_index;
                }
                return r;
            }
            s->preferred_protocol_index = -1;  // re-sniff
        }
    }
    for (int idx : protocols) {
        const Protocol* p = GetProtocol(idx);
        if (p == nullptr || p->parse == nullptr) continue;
        ParseResult r = p->parse(&s->read_buf, s, read_eof, p->parse_arg);
        if (r.error == ParseError::OK) {
            s->preferred_protocol_index = idx;
            r.msg->protocol_index = idx;
            return r;
        }
        if (r.error == ParseError::NOT_ENOUGH_DATA ||
            r.error == ParseError::ERROR) {
            return r;
        }
        // TRY_OTHERS: next protocol.
    }
    return ParseResult::make(s->read_buf.empty() ? ParseError::NOT_ENOUGH_DATA
                                                 : ParseError::TRY_OTHERS);
}

}  // namespace

void InputMessenger::OnNewMessages(Socket* s) {
    InputMessenger* m = (InputMessenger*)s->user();
    if (m == nullptr) return;
    bool read_eof = false;
    // Round scopes (ISSUE 7), flushed once per cut round below: fiber
    // wakeups batch into one futex signal per pool, responses written
    // during the round coalesce into one writev per socket. Chaos mode
    // skips the read-path arming implicitly: injected delays park this
    // fiber, and sched_park flushes + detaches both scopes safely.
    WakeBatcher wake_batch;
    WriteCoalesceScope write_scope;
    // Stage clock: when the latest successful read first / last found
    // bytes. A message's clock starts at the read that brought its first
    // bytes: `first_us` for bytes read into an empty buffer, `last_us`
    // for what a cut leaves behind (the head of the next message came
    // with the tail of this one).
    PumpStamps read_stamps;
    while (!s->Failed()) {
        if (!read_eof) {
            // ICI transport sockets pump their completion queue (identical
            // nr semantics); fd sockets readv (reference
            // input_messenger.cpp:416 checks _rdma_state the same way).
            ssize_t nr;
            // Bytes that begin a message (nothing of it was buffered)
            // start its stage clock at this read's stamp.
            const bool begins_message = s->read_buf.empty();
            if (s->transport() != nullptr) {
                read_stamps = PumpStamps();
                nr = s->transport()->Pump(&s->read_buf, &read_stamps);
            } else if (__builtin_expect(fault_injection_enabled(), 0)) {
                nr = ChaosReadFromFd(s);
            } else {
                nr = s->read_buf.append_from_file_descriptor(s->fd(),
                                                             kReadBurst);
            }
            if (nr > 0) {
                if (s->transport() == nullptr || read_stamps.first_us == 0) {
                    // An fd read, or an endpoint that stamps nothing.
                    read_stamps.first_us = read_stamps.last_us =
                        stage::now_us();
                }
                if (begins_message) s->consumed_us = read_stamps.first_us;
                s->add_bytes_read(nr);
                // Per-tier byte attribution (the Transport seam).
                transport_stats::AddIn(s->transport_tier(), nr);
                // Emulated-WAN shaping, inbound half (ISSUE 14): a
                // dcn-tier socket charges received bytes too — the
                // peer's half of the link is an accepted socket with no
                // forced tier, so without this the response direction
                // would ride the WAN for free. Each direction is shaped
                // exactly once (writes on the dcn socket, reads on the
                // dcn socket). Parking this fiber is legal here, same
                // as the chaos delay path (the round scopes flush and
                // detach on park).
                if (__builtin_expect(s->forced_transport_tier() >= 0, 0) &&
                    DcnShapingEnabled()) {
                    const int64_t d = DcnShapeReadDelayUs(
                        s->transport_tier(), (size_t)nr);
                    if (d > 0) fiber_usleep(d);
                }
            } else if (nr == 0) {
                read_eof = true;
            } else {
                if (errno == EAGAIN || errno == EWOULDBLOCK) {
                    return;  // burst drained; next edge re-triggers
                }
                if (errno == EINTR) continue;
                s->SetFailedWithError(errno);
                return;
            }
        }
        // Cut as many whole messages as the buffer holds. Dispatch policy
        // (run-to-completion, ISSUE 7): small messages of inline-safe
        // protocols process RIGHT HERE on the input fiber while the
        // per-wake budget lasts — no spawn, no context switch, and their
        // response writes coalesce in this round's scope. Past the budget
        // (or for large/unsafe messages) the old fan-out applies: one
        // fiber per message, keeping the LAST message inline for cache
        // locality (reference input_messenger.cpp:194-234 QueueMessage),
        // so a slow handler can't block parsing.
        inline_dispatch::ArmRound();
        InputMessageBase* pending_msg = nullptr;
        const Protocol* pending_proto = nullptr;
        while (!s->read_buf.empty()) {
            ParseResult r = CutInputMessage(s, m->protocols_, read_eof);
            if (r.error == ParseError::OK) {
                r.msg->socket_id = s->id();
                r.msg->consumed_us = s->consumed_us;
                s->consumed_us = read_stamps.last_us;
                const Protocol* p = GetProtocol(r.msg->protocol_index);
                if (p->process_in_order) {
                    // No correlation ids on this protocol: responses must
                    // leave in request order, so run inline right now.
                    p->process(r.msg);
                    continue;
                }
                if (p->inline_safe &&
                    inline_dispatch::Acquire(r.msg->byte_size)) {
                    p->process(r.msg);  // run-to-completion
                    inline_dispatch::EndInlineProcess();
                    continue;
                }
                if (pending_msg != nullptr) {
                    auto* pa = new ProcessArgs{pending_msg, pending_proto};
                    fiber_t tid;
                    if (fiber_start_background(&tid, nullptr,
                                               process_msg_thunk, pa) != 0) {
                        pending_proto->process(pending_msg);
                        delete pa;
                    }
                }
                pending_msg = r.msg;
                pending_proto = p;
                continue;
            }
            if (r.error == ParseError::NOT_ENOUGH_DATA) break;
            // TRY_OTHERS with data left or hard ERROR: broken stream.
            inline_dispatch::DisarmRound();
            s->SetFailedWithError(TERR_REQUEST);
            if (pending_msg != nullptr) pending_proto->process(pending_msg);
            return;
        }
        if (pending_msg != nullptr) {
            pending_proto->process(pending_msg);
        }
        inline_dispatch::DisarmRound();
        // End of round: queued responses leave in one writev per socket,
        // woken fibers get one futex signal per pool.
        write_scope.FlushDeferred();
        wake_batch.Flush();
        if (read_eof) {
            s->SetFailedWithError(TERR_EOF);
            return;
        }
    }
}

}  // namespace tpurpc
