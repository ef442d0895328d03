// SocketMap: the client-side connection registry — one shared connection
// per remote endpoint ("single" connection mode). Modeled on reference
// src/brpc/socket_map.h:82-150 (SocketMapInsert/Remove keyed by endpoint).
#pragma once

#include <deque>
#include <map>
#include <mutex>
#include <vector>

#include "tbase/endpoint.h"
#include "tnet/socket.h"

namespace tpurpc {

class InputMessenger;

// Create a fresh client connection (connect-on-first-write) to `remote`
// fed into `messenger` — the one place client SocketOptions are built
// (SocketMap, SocketPool and short-lived connections all use it).
// `tier` (tnet/transport.h registry id; -1 = default tcp) stamps the
// socket's forced transport tier — how a dcn-class connection differs
// from a tcp one to the same address (ISSUE 14).
int CreateClientSocket(const EndPoint& remote, InputMessenger* messenger,
                       SocketId* id, int tier = -1);

class SocketMap {
public:
    static SocketMap* singleton();

    // Get (or create, connect-on-first-write) the shared socket to `remote`
    // whose input is handled by `messenger`. Returns 0 and sets *id.
    // Keyed by (endpoint, tier) — a tcp and a dcn endpoint at the same
    // address NEVER share a connection or its health/breaker state: a
    // WAN-shaped dcn socket tripping its breaker must not poison the
    // LAN path, and vice versa.
    int GetOrCreate(const EndPoint& remote, InputMessenger* messenger,
                    SocketId* id, int tier = -1);
    // Drop the cached socket (e.g. after SetFailed).
    void Remove(const EndPoint& remote, SocketId expected_id,
                int tier = -1);

    // Every remote this process holds a shared client connection to —
    // the rpcz stitcher's peer discovery (these are real serving ports,
    // unlike accepted connections' ephemeral remote ports).
    std::vector<EndPoint> endpoints();

private:
    // -1 ("default tcp") and an explicit TierTcp() are distinct keys on
    // purpose: normalizing would need the registry initialized before
    // any map use, and nothing creates explicit-tcp entries today.
    using Key = std::pair<EndPoint, int>;
    std::mutex mu_;
    std::map<Key, SocketId> map_;
};

// Pooled ("pooled" connection mode) client sockets: one in-flight RPC per
// connection at a time, returned to the per-remote idle pool after its
// response arrives (reference src/brpc/socket.cpp SocketPool::GetSocket /
// ReturnSocket; controller.cpp: a call that failed without a response
// never reuses its pooled connection). An idle-close sweep fails pooled
// connections unused for -pooled_idle_close_s (reference socket_map.h:204
// idle-close thread).
//
// Selection is FIFO (pop-front / return-push-back), so consecutive calls
// ROUND-ROBIN through the pool members instead of convoying on the most
// recently returned socket: sockets shard across the epoll loops by fd,
// and the old LIFO stack kept re-dispatching the whole pooled load onto
// the one or two hottest fds — the direct cause of pooled-TCP QPS
// landing below single-connection in a pre-PR-1 record (ISSUE 7).
class SocketPool {
public:
    static SocketPool* singleton();

    // Pop the least-recently-used idle healthy connection to `remote` or
    // create a fresh one (connect-on-first-write). Returns 0 and sets
    // *id. Pools are keyed by (endpoint, tier) like the SocketMap — a
    // pooled dcn connection is never handed to a tcp caller.
    int Get(const EndPoint& remote, InputMessenger* messenger, SocketId* id,
            int tier = -1);
    // Return a connection whose RPC received its response. Over-capacity
    // or failed sockets are closed instead of pooled.
    void Return(SocketId id);

    // Test/portal introspection: idle connections pooled for `remote`.
    size_t idle_count(const EndPoint& remote, int tier = -1);

private:
    SocketPool() = default;
    void SweepLoop();  // idle-close fiber

    struct IdleConn {
        SocketId id;
        int64_t returned_us;
    };
    using Key = std::pair<EndPoint, int>;
    std::mutex mu_;
    std::map<Key, std::deque<IdleConn>> pools_;
    bool sweeping_ = false;
};

}  // namespace tpurpc
