// Protocol: the function-pointer table every wire protocol implements, plus
// the global registry.
//
// Modeled on reference src/brpc/protocol.h:77-172 (struct Protocol {parse,
// serialize_request, pack_request, process_request, process_response,
// verify}) and RegisterProtocol/FindProtocol (protocol.h:186-193). The
// InputMessenger sniffs protocols per connection and remembers the winner
// (socket->preferred_protocol_index).
#pragma once

#include <cstdint>
#include <functional>
#include <string>

#include "tbase/iobuf.h"

namespace tpurpc {

class Socket;
class InputMessageBase;

enum class ParseError {
    OK = 0,
    NOT_ENOUGH_DATA,  // keep bytes, wait for more
    TRY_OTHERS,       // not this protocol; let another parser sniff
    ERROR,            // corrupt stream: fail the connection
};

struct ParseResult {
    ParseError error = ParseError::TRY_OTHERS;
    InputMessageBase* msg = nullptr;

    static ParseResult make_ok(InputMessageBase* m) {
        return ParseResult{ParseError::OK, m};
    }
    static ParseResult make(ParseError e) { return ParseResult{e, nullptr}; }
};

// Base of every cut message flowing from parse() to process().
class InputMessageBase {
public:
    virtual ~InputMessageBase() = default;
    // Socket the message arrived on (id; Address() to use).
    uint64_t socket_id = 0;
    int protocol_index = -1;
    // Wire size of this message (header + body), set by parse(). The
    // run-to-completion dispatcher uses it as the "small message" gate
    // (-inline_dispatch_max_bytes); 0 = unknown (never inlined).
    size_t byte_size = 0;
    // Stage clock (tvar/stage_recorder.h): when the read that brought
    // this message's first bytes returned (the link's consume stamp on a
    // shm link), set by the messenger; the protocol's process() takes
    // tnet.consume_to_cut from it.
    int64_t consumed_us = 0;
};

struct Protocol {
    // Cut one message from `source` (bytes already read from the socket).
    ParseResult (*parse)(IOBuf* source, Socket* socket, bool read_eof,
                         const void* arg) = nullptr;
    // Handle a cut message (request on servers, response on clients). Runs
    // on a fiber. Owns `msg` (must delete).
    void (*process)(InputMessageBase* msg) = nullptr;
    // Human name (diagnostics + /connections).
    const char* name = "unknown";
    // Opaque arg passed to parse (e.g. the Server*).
    const void* parse_arg = nullptr;
    // Process every message inline on the input fiber, in cut order.
    // Required by protocols without correlation ids (HTTP): spawning
    // earlier burst messages onto fibers would let responses overtake
    // each other on one connection.
    bool process_in_order = false;
    // Run-to-completion hint (ISSUE 7): process() is cheap and does not
    // block, so small messages may run inline on the input fiber instead
    // of spawning a processing fiber — subject to the per-wake
    // -inline_dispatch_budget (input_messenger.h). Server-side handlers
    // additionally gate on their method's inline-safe flag
    // (Server::SetMethodInlineSafe).
    bool inline_safe = false;

    // ---- zero-cut parse fast path (optional, ISSUE 7) ----
    // Fixed header length `peek` wants to inspect; 0 disables the fast
    // path for this protocol.
    uint32_t peek_len = 0;
    // Classify a sticky connection's next frame from its first peek_len
    // contiguous bytes WITHOUT consuming anything. Returns the total
    // frame size in bytes (>= peek_len; the messenger then waits for the
    // whole frame and calls parse exactly once), 0 when the header is
    // not this protocol's (re-sniff / TRY_OTHERS), or -1 when the header
    // is corrupt (fail the connection). Skips the cutn + re-parse loop
    // the slow path pays on every partial read.
    int64_t (*peek)(const char* hdr, Socket* socket) = nullptr;
};

// Global registry (reference global.cpp:416-601 registers all protocols at
// init). Index is stable after registration.
int RegisterProtocol(const Protocol& p);
const Protocol* GetProtocol(int index);
int ProtocolCount();

}  // namespace tpurpc
