// mesh_node: one member of the multi-process full-mesh chaos soak
// (tests/test_chaos_soak.py drives 8 of these).
//
// Each node is BOTH a server and a client of every peer:
//  - a tpu_std echo Server on 127.0.0.1:--port (with the whole builtin
//    portal: /vars, /chaos, /connections, ...);
//  - an LB channel over "file://<peers>" with the rr balancer —
//    naming-service membership, circuit breaker, health-checked server
//    sockets, retries: the standard client-robustness stack;
//  - one shared-memory ICI link per peer (tici/shm_link.h) carrying the
//    mesh echo traffic, re-established by a maintenance fiber when a
//    peer dies and comes back.
//
// Invariant instrumented here and asserted by the soak: every issued
// RPC terminates (sync calls + a final outstanding==0 check), under
// peer kill, partition (fault injection via each node's /chaos page)
// and heal.
//
// stdin protocol (like echo_bench --ici-server): "stop\n" stops traffic
// and prints one "REPORT {json}" line; EOF shuts the node down
// (Stop+Join, then _exit(0) — exit code 0 only after a clean quiesce).
//
// Delay-heavy phase (the deadline/budget soak): "delay H S\n" makes the
// echo handler sleep H ms and turns on a stale-traffic fiber issuing
// budget-starved calls (1 ms and S ms deadlines) marked req.stale; the
// handler counts executed stale requests so the soak can assert the
// server SHED them (expired / budget-below-service-time) instead of
// executing work nobody will read. "--timeout_cl_ms N" enables the
// server's TimeoutConcurrencyLimiter for the budget-shed path.
#include <netinet/in.h>
#include <signal.h>
#include <sys/prctl.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <memory>
#include <mutex>
#include <string>
#include <vector>

#include "bench_echo.pb.h"
#include "rpc_meta.pb.h"
#include "tbase/endpoint.h"
#include "tbase/errno.h"
#include "tbase/flags.h"
#include "tbase/flight_recorder.h"
#include "tbase/logging.h"
#include "tbase/time.h"
#include "tfiber/fiber.h"
#include "tnet/fault_injection.h"
#include "tnet/transport.h"
#include "trpc/naming_service.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tici/shm_link.h"
#include "tici/verbs.h"
#include "trpc/channel.h"
#include "trpc/collective.h"
#include "trpc/collective_benchpb.h"
#include "trpc/controller.h"
#include "trpc/pb_compat.h"
#include "trpc/policy_tpu_std.h"
#include "trpc/server.h"
#include "trpc/stream.h"
#include "tvar/variable.h"

using namespace tpurpc;

namespace {

// Delay-phase knobs (stdin "delay H S"): handler sleep + stale-call
// budget. Stale executions are the soak's proof of (non-)shedding.
std::atomic<int> g_handler_delay_ms{0};
// Inter-token generation delay of the push-stream handler (ISSUE 17) —
// models a device decode step per token.
std::atomic<int64_t> g_stream_token_delay_us{2000};
std::atomic<int> g_stale_budget_ms{0};
std::atomic<int64_t> g_stale_executed{0};
// --traffic_delay_ms: traffic fibers idle this long after launch so a
// whole mesh can finish listening first. The rolling-restart soak needs
// it: a connect-refused burst at startup would spend retry-budget
// tokens the soak asserts are NEVER spent.
std::atomic<int> g_traffic_delay_ms{0};

struct NodeState;
void TrafficStartDelay(NodeState* st);

// Detached token generator for one accepted push stream (ISSUE 17):
// writes "tok:<key>:<i>" for i = resume_from+1 .. n with a per-token
// delay. DETERMINISTIC in (key, i) — a restarted process regenerates
// exactly the tokens the client has not seen, which is what makes the
// resume exactly-once across process death.
struct StreamGenArgs {
    push_stream::StreamWriter w;
    unsigned long long n = 0;
    std::string key;
};

void* RunStreamGen(void* arg) {
    std::unique_ptr<StreamGenArgs> a((StreamGenArgs*)arg);
    const int64_t delay =
        g_stream_token_delay_us.load(std::memory_order_relaxed);
    for (unsigned long long i = a->w.resume_from() + 1; i <= a->n; ++i) {
        char tok[128];
        snprintf(tok, sizeof(tok), "tok:%s:%llu", a->key.c_str(), i);
        if (a->w.Write(tok, i == a->n) != 0) break;
        if (delay > 0 && i < a->n) fiber_usleep(delay);
    }
    return nullptr;
}

class EchoServiceImpl : public benchpb::EchoService {
public:
    void Echo(google::protobuf::RpcController* cntl_base,
              const benchpb::EchoRequest* request,
              benchpb::EchoResponse* response,
              google::protobuf::Closure* done) override {
        Controller* cntl = static_cast<Controller*>(cntl_base);
        if (request->stale()) {
            g_stale_executed.fetch_add(1, std::memory_order_relaxed);
        }
        const int delay_ms = g_handler_delay_ms.load(std::memory_order_relaxed);
        if (delay_ms > 0) {
            fiber_usleep((int64_t)delay_ms * 1000);
        }
        // Chain forwarding (rpcz stitch soak): pop the head endpoint and
        // call it with the tail FROM INSIDE this handler — the downstream
        // call inherits the remaining deadline, registers for the cancel
        // cascade, and continues this request's trace (its client span
        // parents on this hop's server span).
        if (request->chain_size() > 0) {
            EndPoint next;
            if (str2endpoint(request->chain(0).c_str(), &next) != 0) {
                cntl->SetFailed(22, "bad chain endpoint %s",
                                request->chain(0).c_str());
            } else {
                Channel ch;
                ChannelOptions copts;
                copts.timeout_ms = 2000;  // capped at the inherited budget
                copts.max_retry = 0;
                if (ch.Init(next, &copts) != 0) {
                    cntl->SetFailed(22, "chain channel init failed");
                } else {
                    benchpb::EchoService_Stub stub(&ch);
                    Controller dcntl;
                    benchpb::EchoRequest dreq;
                    benchpb::EchoResponse dres;
                    dreq.set_send_ts_us(monotonic_time_us());
                    for (int i = 1; i < request->chain_size(); ++i) {
                        dreq.add_chain(request->chain(i));
                    }
                    stub.Echo(&dcntl, &dreq, &dres, nullptr);  // sync
                    if (dcntl.Failed()) {
                        cntl->SetFailed(dcntl.ErrorCode(),
                                        "downstream %s: %s",
                                        request->chain(0).c_str(),
                                        dcntl.ErrorText().c_str());
                    }
                }
            }
        }
        // Response-direction descriptor (ISSUE 12): a "desc_rsp:N"
        // payload asks for N bytes answered as a reference into THIS
        // node's pool — the server-side pin the pool chaos soak
        // SIGKILLs clients under (peer death must release it through
        // the socket failure observer, never strand it).
        unsigned long long rsp_n = 0;
        if (sscanf(request->payload().c_str(), "desc_rsp:%llu", &rsp_n) ==
                1 &&
            rsp_n > 0 && rsp_n <= (4u << 20)) {
            IOBuf out;
            char* data = nullptr;
            if (IciBlockPool::AllocatePoolAttachment((size_t)rsp_n, &out,
                                                     &data)) {
                memset(data, 'r', (size_t)rsp_n);
                cntl->set_response_pool_attachment(std::move(out));
            }
        }
        // Push-stream serving (ISSUE 17): a "stream:N:key" payload asks
        // for N tokens streamed after this response. An in-place resume
        // (same process, generator still live) must NOT start a second
        // generator — the replay ring + the rebound writer continue it.
        unsigned long long stream_n = 0;
        char stream_key[64] = {0};
        if (sscanf(request->payload().c_str(), "stream:%llu:%63s",
                   &stream_n, stream_key) == 2 &&
            stream_n > 0 && stream_n <= (1u << 20)) {
            push_stream::StreamWriter w = cntl->accept_stream();
            if (!w.valid()) {
                cntl->SetFailed(TERR_REQUEST,
                                "stream payload without push open");
            } else if (!w.resumed_in_place()) {
                auto* a = new StreamGenArgs;
                a->w = w;
                a->n = stream_n;
                a->key = stream_key;
                fiber_t tid;
                if (fiber_start_background(&tid, nullptr, RunStreamGen,
                                           a) != 0) {
                    delete a;
                    w.Abort(TERR_INTERNAL);
                    cntl->SetFailed(TERR_INTERNAL,
                                    "stream generator spawn failed");
                }
            }
        }
        response->set_send_ts_us(request->send_ts_us());
        cntl->response_attachment().append(cntl->request_attachment());
        done->Run();
    }
};

struct Counters {
    std::atomic<int64_t> lb_issued{0}, lb_ok{0}, lb_failed{0};
    std::atomic<int64_t> shm_issued{0}, shm_ok{0}, shm_failed{0};
    // Collective rounds driven by this node (ISSUE 13): every issued
    // round terminates (ok or failed — zero lost completions), and a
    // completed round's result is VERIFIED against the deterministic
    // inputs of the membership it completed over (verify_failed must
    // stay 0 through kills and re-forms).
    std::atomic<int64_t> coll_issued{0}, coll_ok{0}, coll_failed{0};
    std::atomic<int64_t> coll_verify_failed{0};
    std::atomic<int64_t> coll_nranks_last{0};
    std::atomic<int64_t> stale_issued{0}, stale_ok{0}, stale_failed{0};
    // One-sided descriptor traffic (ISSUE 10): every call pins a pool
    // block under a lease; desc_stale counts TERR_STALE_EPOCH fences
    // (EXPECTED retriable failures under chaos_pool stale injection).
    std::atomic<int64_t> desc_issued{0}, desc_ok{0}, desc_failed{0};
    std::atomic<int64_t> desc_stale{0};
    // One-sided verb traffic (ISSUE 18): REMOTE_WRITE + REMOTE_READ
    // round-trips against leased peer windows; verbs_stale counts
    // TERR_STALE_EPOCH fences (expected retriable failures under
    // pool_stale chaos), regrants counts window (re-)grants.
    std::atomic<int64_t> verbs_issued{0}, verbs_ok{0}, verbs_failed{0};
    std::atomic<int64_t> verbs_stale{0}, verbs_regrants{0};
    // Response-direction descriptors resolved by this node's CLIENT
    // side (ISSUE 12): desc_rsp_ok counts calls whose answer arrived as
    // a verified in-place view of the peer's pool.
    std::atomic<int64_t> desc_rsp_issued{0}, desc_rsp_ok{0};
    std::atomic<int64_t> expired_probes{0};
    std::atomic<int64_t> outstanding{0};
    std::atomic<int64_t> reconnects{0};
};

// One link to a peer; the channel is replaced on reconnect (a Channel
// pins one socket for its lifetime). Intra-pod peers ride shm-ICI
// links; cross-pod peers (--dcn_peers, ISSUE 14) ride pinned dcn-tier
// channels — plain TCP flagged dcn, so descriptors degrade to inline,
// bytes land on rpc_transport_*{transport="dcn"}, and the -dcn_emu_*
// knobs shape them.
struct PeerLink {
    EndPoint ep;
    bool dcn = false;
    std::string zone;  // the peer's zone ("" = mine)
    std::mutex mu;
    std::shared_ptr<Channel> ch;  // null until connected
};

struct NodeState {
    std::vector<std::unique_ptr<PeerLink>> links;
    std::unique_ptr<Channel> lb_channel;
    Counters counters;
    std::atomic<bool> stop{false};
    // Traffic fibers, joinable from EITHER the stdin "stop" path or the
    // SIGTERM graceful-quit watcher — the exchange guard keeps the join
    // single-shot (double fiber_join is UB).
    std::vector<fiber_t> traffic_fibers;
    std::atomic<bool> fibers_joined{false};
    // Tells the GracefulQuitWatcher fiber to exit: it holds raw pointers
    // to main()'s stack-local Server/NodeState, so the stdin-EOF
    // teardown must stop and JOIN it before those objects die.
    std::atomic<bool> watcher_stop{false};

    void StopTraffic() {
        stop.store(true, std::memory_order_relaxed);
        if (!fibers_joined.exchange(true, std::memory_order_acq_rel)) {
            for (fiber_t t : traffic_fibers) fiber_join(t, nullptr);
        }
    }
};

void TrafficStartDelay(NodeState* st) {
    const int64_t until =
        monotonic_time_us() +
        (int64_t)g_traffic_delay_ms.load(std::memory_order_relaxed) * 1000;
    while (monotonic_time_us() < until &&
           !st->stop.load(std::memory_order_relaxed)) {
        fiber_usleep(20 * 1000);
    }
}

// ---------------- collectives (ISSUE 13) ----------------

int g_my_port = 0;
std::string g_my_zone;  // --zone (also sets -rpc_zone)

// Live membership from the mesh's link table: a peer is a member while
// its shm channel is up (LinkMaintenanceFiber re-establishes dead ones,
// so a restarted node rejoins the collective automatically). Keys are
// listen ports — stable, unique, and identical in every node's view.
class MeshMembership : public CollectiveMembership {
public:
    explicit MeshMembership(NodeState* st) : st_(st) {}
    void GetMembers(std::vector<Member>* out) override {
        Member self;
        self.key = (uint64_t)g_my_port;
        self.self = true;
        self.zone = g_my_zone;
        out->push_back(self);
        for (auto& lp : st_->links) {
            std::shared_ptr<Channel> ch;
            {
                std::lock_guard<std::mutex> g(lp->mu);
                ch = lp->ch;
            }
            if (ch == nullptr) continue;
            SocketUniquePtr s = SocketUniquePtr::FromId(ch->pinned_socket());
            if (!s || s->Failed()) continue;
            Member m;
            m.key = (uint64_t)lp->ep.port;
            m.chan = ch;
            m.zone = lp->dcn ? lp->zone : g_my_zone;
            out->push_back(m);
        }
    }

private:
    NodeState* st_;
};

CollectiveEngine* g_coll_engine = nullptr;

class CollectiveServiceImpl : public benchpb::CollectiveService {
public:
    void Exchange(google::protobuf::RpcController* cntl_base,
                  const benchpb::CollChunk* req, benchpb::CollAck* res,
                  google::protobuf::Closure* done) override {
        HandleCollectiveExchange(g_coll_engine,
                                 static_cast<Controller*>(cntl_base), req,
                                 res, done);
    }
};

// Deterministic collective inputs: every node can reconstruct every
// member's contribution from (seq, key) alone, so each node VERIFIES
// each completed round bit-for-bit — the strongest possible
// lost/corrupt-chunk detector under chaos. A2A pair payloads fold both
// endpoints into the key.
uint64_t A2aKey(uint64_t src_key, uint64_t dst_key) {
    return src_key * 1000003ull + dst_key;
}

struct CollRunArgs {
    NodeState* st = nullptr;
    std::string alg;     // allreduce | allreduce_serial | allgather | alltoall
    uint64_t bytes = 0;  // per-kind meaning (payload / block)
    uint64_t seq = 0;
    bool print = false;  // stdin-commanded round: emit a COLL line
};

// Runs ONE collective round, verifies it, updates counters; returns ok.
bool RunCollectiveRound(const CollRunArgs& a) {
    CollectiveEngine* eng = g_coll_engine;
    if (eng == nullptr) return false;
    Counters& c = a.st->counters;
    c.outstanding.fetch_add(1);
    c.coll_issued.fetch_add(1);
    CollectiveEngine::Result r;
    bool ok = false;
    bool verified = true;
    uint32_t checksum = 0;
    std::vector<uint32_t> head;
    double busbw = 0.0;
    uint64_t moved_total = 0;
    const uint64_t my_key = (uint64_t)g_my_port;

    // Lane-pinned stdin variants (ISSUE 18): "allreduce_verbs" /
    // "allreduce_chunks" select the ring's transport for THIS round —
    // bench verbs_scrape drives one of each and compares the
    // allreduce_verbs vs allreduce busbw gauges. The driver serializes
    // commanded rounds, so flipping the engine flag here is safe.
    std::string alg = a.alg;
    if (alg == "allreduce_verbs" || alg == "allreduce_chunks") {
        eng->set_verbs_lane(alg == "allreduce_verbs");
        alg = "allreduce";
    }

    if (alg == "allreduce" || alg == "allreduce_serial" ||
        alg == "hier_allreduce") {
        const size_t nwords = (size_t)(a.bytes / 4 ? a.bytes / 4 : 1);
        std::vector<uint32_t> words(nwords);
        CollectiveEngine::FillDeterministic(a.seq, my_key, words.data(),
                                            nwords);
        // hier (ISSUE 14): intra-pod ring + leader exchange over dcn +
        // broadcast ring — verified exactly like the flat all-reduce,
        // against the CONTRIBUTING key set the engine reports.
        const int err =
            alg == "allreduce"
                ? eng->AllReduce(a.seq, words.data(), nwords, &r)
                : alg == "hier_allreduce"
                      ? eng->HierAllReduce(a.seq, words.data(), nwords, &r)
                      : eng->SerialAllReduce(a.seq, words.data(), nwords,
                                             &r);
        ok = err == 0;
        if (ok) {
            // expected[i] = sum of every member's deterministic word.
            std::vector<uint32_t> expect(nwords, 0);
            std::vector<uint32_t> tmp(nwords);
            for (uint64_t k : r.member_keys) {
                CollectiveEngine::FillDeterministic(a.seq, k, tmp.data(),
                                                    nwords);
                for (size_t i = 0; i < nwords; ++i) expect[i] += tmp[i];
            }
            verified = expect == words;
            checksum = CollectiveEngine::Checksum(words.data(), nwords);
            for (size_t i = 0; i < nwords && i < 4; ++i) {
                head.push_back(words[i]);
            }
            moved_total = nwords * 4;
        }
    } else if (alg == "allgather") {
        const size_t block = (size_t)(a.bytes ? a.bytes & ~3ull : 4);
        std::vector<uint32_t> mine(block / 4);
        CollectiveEngine::FillDeterministic(a.seq, my_key, mine.data(),
                                            mine.size());
        std::string out;
        ok = eng->AllGather(a.seq, mine.data(), block, &out, &r) == 0;
        if (ok) {
            std::string expect;
            std::vector<uint32_t> tmp(block / 4);
            for (uint64_t k : r.member_keys) {
                CollectiveEngine::FillDeterministic(a.seq, k, tmp.data(),
                                                    tmp.size());
                expect.append((const char*)tmp.data(), block);
            }
            verified = expect == out;
            checksum = CollectiveEngine::Checksum(
                (const uint32_t*)out.data(), out.size() / 4);
            moved_total = out.size();
        }
    } else if (alg == "alltoall") {
        const size_t block = (size_t)(a.bytes ? a.bytes & ~3ull : 4);
        // Blocks for every POSSIBLE member (self + all configured
        // peers) so a re-formed round still finds its payloads.
        std::map<uint64_t, std::string> blocks;
        std::vector<uint32_t> tmp(block / 4);
        auto fill_for = [&](uint64_t dst_key) {
            CollectiveEngine::FillDeterministic(
                a.seq, A2aKey(my_key, dst_key), tmp.data(), tmp.size());
            blocks[dst_key].assign((const char*)tmp.data(), block);
        };
        fill_for(my_key);
        for (auto& lp : a.st->links) fill_for((uint64_t)lp->ep.port);
        std::string out;
        ok = eng->AllToAll(a.seq, blocks, block, &out, &r) == 0;
        if (ok) {
            std::string expect;
            for (uint64_t k : r.member_keys) {
                CollectiveEngine::FillDeterministic(
                    a.seq, A2aKey(k, my_key), tmp.data(), tmp.size());
                expect.append((const char*)tmp.data(), block);
            }
            verified = expect == out;
            checksum = CollectiveEngine::Checksum(
                (const uint32_t*)out.data(), out.size() / 4);
            moved_total = out.size();
        }
    }

    if (ok) {
        busbw = r.busbw_mbps;  // computed once, in the engine
        c.coll_ok.fetch_add(1);
        c.coll_nranks_last.store(r.nranks, std::memory_order_relaxed);
        if (!verified) c.coll_verify_failed.fetch_add(1);
    } else {
        c.coll_failed.fetch_add(1);
    }
    c.outstanding.fetch_sub(1);

    if (a.print) {
        std::string head_s;
        char num[16];
        for (uint32_t v : head) {
            snprintf(num, sizeof(num), "%s%u", head_s.empty() ? "" : ",",
                     v);
            head_s += num;
        }
        printf(
            "COLL {\"alg\": \"%s\", \"seq\": %llu, \"ok\": %d, "
            "\"verified\": %d, \"error\": %d, \"nranks\": %u, "
            "\"bytes\": %llu, \"elapsed_us\": %lld, "
            "\"busbw_mbps\": %.1f, \"checksum\": %u, \"head\": [%s], "
            "\"reforms\": %d, \"retries\": %d, "
            "\"desc_fallback_chunks\": %llu, "
            "\"verb_steps\": %llu, \"verb_fallback_chunks\": %llu}\n",
            a.alg.c_str(), (unsigned long long)a.seq, ok ? 1 : 0,
            verified ? 1 : 0, r.error, r.nranks,
            (unsigned long long)moved_total, (long long)r.elapsed_us,
            busbw, checksum, head_s.c_str(), r.reforms, r.retries,
            (unsigned long long)r.desc_fallback_chunks,
            (unsigned long long)r.verb_steps,
            (unsigned long long)r.verb_fallback_chunks);
        fflush(stdout);
    }
    return ok && verified;
}

void* CollCommandFiber(void* arg) {
    std::unique_ptr<CollRunArgs> a((CollRunArgs*)arg);
    RunCollectiveRound(*a);
    return nullptr;
}

// Continuous collective traffic (--coll_traffic): the same program on
// every node — mostly all-reduce (the soak SIGKILLs a node mid-op),
// with all-gather and all-to-all rounds mixed in on a fixed schedule
// so all nodes stay round-aligned.
void* CollTrafficFiber(void* arg) {
    auto* st = (NodeState*)arg;
    TrafficStartDelay(st);
    uint64_t seq = 0;
    CollRunArgs a;
    a.st = st;
    while (!st->stop.load(std::memory_order_relaxed)) {
        // Adopt the mesh's current round when (re)joining: peers
        // mid-round N must not wait on a node restarting from 1.
        CollectiveEngine* eng = g_coll_engine;
        const uint64_t observed = eng != nullptr ? eng->ObservedSeq() : 0;
        seq = seq + 1 > observed ? seq + 1 : observed;
        a.seq = seq;
        // With dcn peers configured (two-pod topology, ISSUE 14) the
        // mix leans on hierarchical all-reduce — the operation the
        // whole-pod-partition soak must prove re-forms over the
        // surviving pod. Every node derives the same schedule from seq.
        const bool have_dcn = [&] {
            for (auto& lp : st->links) {
                if (lp->dcn) return true;
            }
            return false;
        }();
        if (seq % 5 == 2) {
            a.alg = "allgather";
            a.bytes = 32 << 10;  // per-rank block
        } else if (seq % 5 == 4) {
            a.alg = "alltoall";
            a.bytes = 16 << 10;  // per-pair block
        } else if (have_dcn && seq % 5 != 0) {
            a.alg = "hier_allreduce";
            a.bytes = 256 << 10;
        } else {
            a.alg = "allreduce";
            a.bytes = have_dcn ? 128 << 10 : 512 << 10;  // payload
        }
        RunCollectiveRound(a);
        fiber_usleep(50 * 1000);
    }
    return nullptr;
}

// In-process numeric tvar read (the REPORT line carries re-issue and
// drain counters so the rolling-restart soak can assert on DYING
// incarnations whose portal is gone by assertion time).
int64_t VarInt(const char* name) {
    std::string v;
    if (!Variable::describe_exposed(name, &v)) return 0;
    return atoll(v.c_str());
}

// QoS identity of this node's own traffic (--tenant/--priority): the
// mesh's background load can then be classed against foreground load in
// the overload soak (unset = the default tenant/priority class).
std::string g_tenant;
std::atomic<int> g_priority{-1};

bool DoEcho(Channel* ch, int64_t timeout_ms, const std::string& payload) {
    benchpb::EchoService_Stub stub(ch);
    Controller cntl;
    cntl.set_timeout_ms(timeout_ms);
    if (!g_tenant.empty()) cntl.set_tenant(g_tenant);
    const int prio = g_priority.load(std::memory_order_relaxed);
    if (prio >= 0) cntl.set_priority(prio);
    benchpb::EchoRequest req;
    benchpb::EchoResponse res;
    req.set_send_ts_us(monotonic_time_us());
    cntl.request_attachment().append(payload);
    stub.Echo(&cntl, &req, &res, nullptr);  // sync: termination is proven
    return !cntl.Failed();
}

void* LbTrafficFiber(void* arg) {
    auto* st = (NodeState*)arg;
    TrafficStartDelay(st);
    const std::string payload(128, 'b');
    while (!st->stop.load(std::memory_order_relaxed)) {
        st->counters.outstanding.fetch_add(1);
        st->counters.lb_issued.fetch_add(1);
        if (DoEcho(st->lb_channel.get(), 800, payload)) {
            st->counters.lb_ok.fetch_add(1);
        } else {
            st->counters.lb_failed.fetch_add(1);
        }
        st->counters.outstanding.fetch_sub(1);
        fiber_usleep(3000);
    }
    return nullptr;
}

void* ShmTrafficFiber(void* arg) {
    auto* st = (NodeState*)arg;
    TrafficStartDelay(st);
    const std::string payload(128, 's');
    size_t next = 0;
    while (!st->stop.load(std::memory_order_relaxed)) {
        if (st->links.empty()) break;
        PeerLink& link = *st->links[next++ % st->links.size()];
        std::shared_ptr<Channel> ch;
        {
            std::lock_guard<std::mutex> g(link.mu);
            ch = link.ch;
        }
        if (ch != nullptr) {
            st->counters.outstanding.fetch_add(1);
            st->counters.shm_issued.fetch_add(1);
            if (DoEcho(ch.get(), 800, payload)) {
                st->counters.shm_ok.fetch_add(1);
            } else {
                st->counters.shm_failed.fetch_add(1);
            }
            st->counters.outstanding.fetch_sub(1);
        }
        fiber_usleep(3000);
    }
    return nullptr;
}

// One-sided descriptor traffic (--desc_traffic, ISSUE 10): every call
// pins a fresh pool block under a lease and posts it as a
// (pool_id, offset, len, crc, epoch) reference over the shm links —
// the zero-copy path the pool chaos soak SIGKILLs nodes under. The
// invariants the soak asserts ride the REPORT line: every issued call
// terminates, the lease ledger returns to pinned=0 after quiesce, and
// stale-epoch fences fail ONLY the call (counted desc_stale, the node
// keeps serving).
void* DescTrafficFiber(void* arg) {
    auto* st = (NodeState*)arg;
    TrafficStartDelay(st);
    constexpr size_t kDescBytes = 48 * 1024;
    size_t next = 0;
    while (!st->stop.load(std::memory_order_relaxed)) {
        if (st->links.empty()) break;
        PeerLink& link = *st->links[next++ % st->links.size()];
        std::shared_ptr<Channel> ch;
        {
            std::lock_guard<std::mutex> g(link.mu);
            ch = link.ch;
        }
        if (ch != nullptr) {
            st->counters.outstanding.fetch_add(1);
            st->counters.desc_issued.fetch_add(1);
            IOBuf att;
            char* data = nullptr;
            bool ok = false;
            bool stale = false;
            if (IciBlockPool::AllocatePoolAttachment(kDescBytes, &att,
                                                     &data)) {
                memset(data, (int)('a' + next % 26), kDescBytes);
                benchpb::EchoService_Stub stub(ch.get());
                Controller cntl;
                cntl.set_timeout_ms(800);
                cntl.set_request_pool_attachment(std::move(att));
                benchpb::EchoRequest req;
                benchpb::EchoResponse res;
                // Symmetric round (ISSUE 12): ask the peer to answer
                // with a response-direction descriptor too, so kills
                // and chaos hit pins in BOTH directions.
                char ask[48];
                snprintf(ask, sizeof(ask), "desc_rsp:%zu", kDescBytes);
                req.set_payload(ask);
                st->counters.desc_rsp_issued.fetch_add(1);
                req.set_send_ts_us(monotonic_time_us());
                stub.Echo(&cntl, &req, &res, nullptr);  // sync
                ok = !cntl.Failed();
                stale = cntl.ErrorCode() == TERR_STALE_EPOCH;
                if (ok &&
                    cntl.response_pool_attachment().length ==
                        kDescBytes &&
                    cntl.response_pool_attachment().data != nullptr &&
                    cntl.response_pool_attachment().data[0] == 'r') {
                    st->counters.desc_rsp_ok.fetch_add(1);
                }
                // Controller teardown here acks the peer's rsp pin.
            }
            if (ok) {
                st->counters.desc_ok.fetch_add(1);
            } else {
                st->counters.desc_failed.fetch_add(1);
                if (stale) st->counters.desc_stale.fetch_add(1);
            }
            st->counters.outstanding.fetch_sub(1);
        }
        fiber_usleep(4000);
    }
    return nullptr;
}

// One-sided verb traffic (--verbs_traffic, ISSUE 18): each round leases
// a 64 KB window in a peer's pool, REMOTE_WRITEs a patterned payload
// through a 4-entry scatter-gather list, then REMOTE_READs it back and
// verifies byte-for-byte — the round-trip the verb chaos soak SIGKILLs
// nodes under. Windows are cached per link and re-granted on failure,
// near lease expiry, or after a reconnect rebinds the link's socket; a
// window dropped on the floor is reclaimed by the grantor's lease
// reaper (pinned must still drain to 0). dcn links ride the emulated
// two-sided wire path — same verbs, degraded transport.
constexpr uint64_t kMeshWrTag = 0x4D45ull << 48;  // 'ME'
std::atomic<uint64_t> g_mesh_wr{1};

// Mesh wr ids are salted with the pid (bits 32..47) so ids are unique
// ACROSS nodes, not just within one: the black-box merge pairs an
// initiator's VERB_POST with the grantor's VERB_WIRE by wr id, and a
// bare per-process counter would collide between initiators.
uint64_t NextMeshWr() {
    static const uint64_t salt = ((uint64_t)(getpid() & 0xffff)) << 32;
    return kMeshWrTag | salt | (g_mesh_wr.fetch_add(1) & 0xffffffffu);
}

// Parks until the CQ delivers wr_id (this fiber posts one verb at a
// time, so no other completion can appear). The 8 s bound is far
// beyond the verb plane's post-timeout terminal guarantee — a pending
// post can never outlive the caller's stack CQ.
bool ParkForWr(verbs::CompletionQueue* cq, uint64_t wr,
               verbs::Completion* out) {
    const int64_t give_up = monotonic_time_us() + 8 * 1000 * 1000;
    while (monotonic_time_us() < give_up) {
        if (!cq->Park(out, 500 * 1000)) continue;
        if (out->wr_id == wr) return true;
    }
    return false;
}

void* VerbsTrafficFiber(void* arg) {
    auto* st = (NodeState*)arg;
    TrafficStartDelay(st);
    constexpr size_t kVerbBytes = 64 * 1024;
    constexpr uint32_t kNsge = 4;
    verbs::CompletionQueue cq;
    std::vector<verbs::RemoteWindow> wins(st->links.size());
    std::vector<char> wr_buf(kVerbBytes), rd_buf(kVerbBytes);
    size_t next = 0;
    uint64_t round = 0;
    while (!st->stop.load(std::memory_order_relaxed)) {
        if (st->links.empty()) break;
        const size_t li = next++ % st->links.size();
        PeerLink& link = *st->links[li];
        std::shared_ptr<Channel> ch;
        {
            std::lock_guard<std::mutex> g(link.mu);
            ch = link.ch;
        }
        if (ch == nullptr) {
            fiber_usleep(5000);
            continue;
        }
        const uint64_t sid = (uint64_t)ch->pinned_socket();
        st->counters.outstanding.fetch_add(1);
        st->counters.verbs_issued.fetch_add(1);
        verbs::RemoteWindow& w = wins[li];
        bool ok = false;
        bool stale = false;
        if (w.window_id == 0 || w.peer != sid ||
            (w.deadline_us != 0 &&
             monotonic_time_us() > w.deadline_us - 500 * 1000)) {
            w = verbs::RemoteWindow();
            if (verbs::RequestWindow(sid, kVerbBytes,
                                     verbs::kWinRead | verbs::kWinWrite,
                                     800, &w) == 0) {
                st->counters.verbs_regrants.fetch_add(1);
            }
        }
        if (w.window_id != 0) {
            ++round;
            for (size_t i = 0; i < kVerbBytes; ++i) {
                wr_buf[i] = (char)('a' + (round + i) % 26);
            }
            // 4-entry SGL: the write gathers local pieces, the
            // read-back scatters into a second buffer.
            const size_t piece = kVerbBytes / kNsge;
            verbs::Sge sgl[kNsge];
            for (uint32_t i = 0; i < kNsge; ++i) {
                sgl[i].addr = wr_buf.data() + i * piece;
                sgl[i].len = piece;
            }
            const uint64_t wid = NextMeshWr();
            verbs::Completion comp;
            if (verbs::PostWrite(&cq, wid, w, 0, sgl, kNsge) == 0 &&
                ParkForWr(&cq, wid, &comp)) {
                stale = comp.status == TERR_STALE_EPOCH;
                if (comp.status == 0) {
                    memset(rd_buf.data(), 0, kVerbBytes);
                    for (uint32_t i = 0; i < kNsge; ++i) {
                        sgl[i].addr = rd_buf.data() + i * piece;
                    }
                    const uint64_t rid = NextMeshWr();
                    if (verbs::PostRead(&cq, rid, w, 0, sgl, kNsge) ==
                            0 &&
                        ParkForWr(&cq, rid, &comp)) {
                        stale = comp.status == TERR_STALE_EPOCH;
                        ok = comp.status == 0 &&
                             comp.bytes == kVerbBytes &&
                             memcmp(wr_buf.data(), rd_buf.data(),
                                    kVerbBytes) == 0;
                    }
                }
            }
            if (!ok) w = verbs::RemoteWindow();  // re-grant next visit
        }
        if (ok) {
            st->counters.verbs_ok.fetch_add(1);
        } else {
            st->counters.verbs_failed.fetch_add(1);
            if (stale) st->counters.verbs_stale.fetch_add(1);
        }
        st->counters.outstanding.fetch_sub(1);
        fiber_usleep(4000);
    }
    cq.Shutdown();
    return nullptr;
}

// Delay-phase client: issues budget-starved calls against the LB plane.
// Two flavors per round, a 1 ms deadline (the minimum the stamp floor
// produces) and a g_stale_budget_ms deadline — both positive but below
// the handler-delay-taught service time, so the
// TimeoutConcurrencyLimiter's budget check sheds them at admission.
// Both fail client-side fast; the invariant is that the server did not
// EXECUTE them (g_stale_executed stays low).
void* StaleTrafficFiber(void* arg) {
    auto* st = (NodeState*)arg;
    while (!st->stop.load(std::memory_order_relaxed)) {
        const int budget_ms = g_stale_budget_ms.load(std::memory_order_relaxed);
        if (budget_ms <= 0) {
            fiber_usleep(20 * 1000);
            continue;
        }
        const int64_t budgets[2] = {1, budget_ms};
        for (int k = 0; k < 2; ++k) {
            if (st->stop.load(std::memory_order_relaxed)) break;
            st->counters.outstanding.fetch_add(1);
            st->counters.stale_issued.fetch_add(1);
            benchpb::EchoService_Stub stub(st->lb_channel.get());
            Controller cntl;
            cntl.set_timeout_ms(budgets[k]);
            cntl.set_max_retry(0);  // a doomed call must not re-issue
            benchpb::EchoRequest req;
            benchpb::EchoResponse res;
            req.set_send_ts_us(monotonic_time_us());
            req.set_stale(true);
            stub.Echo(&cntl, &req, &res, nullptr);  // sync: terminates
            if (cntl.Failed()) {
                st->counters.stale_failed.fetch_add(1);
            } else {
                st->counters.stale_ok.fetch_add(1);
            }
            st->counters.outstanding.fetch_sub(1);
        }
        fiber_usleep(15 * 1000);
    }
    return nullptr;
}

// Delay-phase raw probe: handcrafted tpu_std frames stamped
// timeout_ms=0 — the wire shape of "the client already gave up" (a
// conforming client floors live budgets at 1 ms, so 0 only appears when
// the deadline truly passed). The server must reject these BEFORE
// admission, parse, or user code (rpc_server_expired_requests); they
// can never reach the handler, so g_stale_executed is structurally
// untouched by them.
void* ExpiredProbeFiber(void* arg) {
    auto* st = (NodeState*)arg;
    int fd = -1;
    uint64_t probe_cid = 1;
    while (!st->stop.load(std::memory_order_relaxed)) {
        if (g_stale_budget_ms.load(std::memory_order_relaxed) <= 0 ||
            st->links.empty()) {
            if (fd >= 0) {
                close(fd);
                fd = -1;
            }
            fiber_usleep(20 * 1000);
            continue;
        }
        if (fd < 0) {
            fd = ::socket(AF_INET, SOCK_STREAM, 0);
            sockaddr_in addr;
            endpoint2sockaddr(st->links[0]->ep, &addr);
            if (fd < 0 ||
                ::connect(fd, (sockaddr*)&addr, sizeof(addr)) != 0) {
                if (fd >= 0) {
                    close(fd);
                    fd = -1;
                }
                fiber_usleep(100 * 1000);
                continue;
            }
        }
        rpc::RpcMeta meta;
        auto* rm = meta.mutable_request();
        rm->set_service_name("benchpb.EchoService");
        rm->set_method_name("Echo");
        rm->set_timeout_ms(0);  // expired on arrival, by construction
        meta.set_correlation_id(probe_cid++);
        benchpb::EchoRequest req;
        req.set_stale(true);
        IOBuf meta_buf, payload;
        SerializePbToIOBuf(meta, &meta_buf);
        SerializePbToIOBuf(req, &payload);
        IOBuf frame;
        PackTpuStdFrame(&frame, meta_buf, payload, IOBuf());
        const std::string wire = frame.to_string();
        if (::send(fd, wire.data(), wire.size(), MSG_NOSIGNAL) !=
            (ssize_t)wire.size()) {
            close(fd);
            fd = -1;
            continue;
        }
        st->counters.expired_probes.fetch_add(1);
        // Drain the error responses without blocking the worker.
        char drain[4096];
        while (::recv(fd, drain, sizeof(drain), MSG_DONTWAIT) > 0) {
        }
        fiber_usleep(30 * 1000);
    }
    if (fd >= 0) close(fd);
    return nullptr;
}

// Keeps the mesh connected: (re-)establishes any link whose pinned
// socket died — a killed peer that comes back on the same port rejoins
// the mesh here.
void* LinkMaintenanceFiber(void* arg) {
    auto* st = (NodeState*)arg;
    while (!st->stop.load(std::memory_order_relaxed)) {
        for (auto& lp : st->links) {
            if (st->stop.load(std::memory_order_relaxed)) break;
            PeerLink& link = *lp;
            bool dead;
            {
                std::lock_guard<std::mutex> g(link.mu);
                if (link.ch == nullptr) {
                    dead = true;
                } else {
                    SocketUniquePtr s =
                        SocketUniquePtr::FromId(link.ch->pinned_socket());
                    dead = !s || s->Failed();
                }
            }
            if (!dead) continue;
            auto fresh = std::make_shared<Channel>();
            ChannelOptions copts;
            copts.timeout_ms = 800;
            copts.max_retry = 0;  // the maintenance loop IS the retry
            bool up = false;
            if (link.dcn) {
                // Cross-pod link (ISSUE 14): a pinned dcn-tier channel.
                // Plain TCP connects lazily, so prove the peer is
                // really there with one short probe echo before
                // installing — the membership view (pinned socket not
                // failed) must mean "verified reachable", exactly what
                // the shm handshake gives intra-pod links.
                copts.transport = "dcn";
                copts.pin_connection = true;
                if (fresh->Init(link.ep, &copts) == 0) {
                    benchpb::EchoService_Stub stub(fresh.get());
                    Controller probe;
                    probe.set_timeout_ms(400);
                    probe.set_max_retry(0);
                    benchpb::EchoRequest req;
                    benchpb::EchoResponse res;
                    req.set_send_ts_us(monotonic_time_us());
                    stub.Echo(&probe, &req, &res, nullptr);  // sync
                    up = !probe.Failed();
                    if (!up) {
                        // Don't leak a half-open pinned connection.
                        Socket::SetFailedById(fresh->pinned_socket());
                    }
                }
            } else {
                up = fresh->InitIci(link.ep, &copts) == 0;
            }
            if (up) {
                std::lock_guard<std::mutex> g(link.mu);
                const bool was_connected = link.ch != nullptr;
                link.ch = std::move(fresh);
                if (was_connected) st->counters.reconnects.fetch_add(1);
            }
        }
        fiber_usleep(300 * 1000);
    }
    return nullptr;
}

// One root call of the stitch soak ("chain T ep1 ep2..." on stdin): Echo
// to ep1 with chain=[ep2...] under a T-ms deadline, then print the trace
// id so the driving test can fetch /rpcz/trace/<id>. Runs on a fiber
// (sync RPC) — the stdin loop stays responsive.
struct ChainArgs {
    int64_t timeout_ms = 1000;
    std::vector<std::string> eps;
};

void* ChainCallFiber(void* arg) {
    std::unique_ptr<ChainArgs> a((ChainArgs*)arg);
    EndPoint first;
    if (a->eps.empty() || str2endpoint(a->eps[0].c_str(), &first) != 0) {
        printf("CHAIN trace=0 err=22\n");
        fflush(stdout);
        return nullptr;
    }
    Channel ch;
    ChannelOptions copts;
    copts.timeout_ms = a->timeout_ms;
    copts.max_retry = 0;
    if (ch.Init(first, &copts) != 0) {
        printf("CHAIN trace=0 err=112\n");
        fflush(stdout);
        return nullptr;
    }
    benchpb::EchoService_Stub stub(&ch);
    Controller cntl;
    cntl.set_timeout_ms(a->timeout_ms);
    benchpb::EchoRequest req;
    benchpb::EchoResponse res;
    req.set_send_ts_us(monotonic_time_us());
    for (size_t i = 1; i < a->eps.size(); ++i) {
        req.add_chain(a->eps[i]);
    }
    stub.Echo(&cntl, &req, &res, nullptr);  // sync: trace id is final
    printf("CHAIN trace=%llu err=%d\n",
           (unsigned long long)cntl.trace_id(), cntl.ErrorCode());
    fflush(stdout);
    return nullptr;
}

void PrintReport(int id, int port, const Counters& c) {
    // Client re-issue + drain counters ride the report so the soak can
    // assert "zero retry tokens spent" even for an incarnation that is
    // about to exit (its /vars portal dies with it).
    const long long reissues =
        VarInt("rpc_client_retries") + VarInt("rpc_client_backup_requests");
    printf(
        "REPORT {\"id\": %d, \"port\": %d, \"lb_issued\": %lld, "
        "\"lb_ok\": %lld, \"lb_failed\": %lld, \"shm_issued\": %lld, "
        "\"shm_ok\": %lld, \"shm_failed\": %lld, "
        "\"coll_issued\": %lld, \"coll_ok\": %lld, "
        "\"coll_failed\": %lld, \"coll_verify_failed\": %lld, "
        "\"coll_nranks\": %lld, \"coll_ops\": %lld, "
        "\"coll_steps\": %lld, \"coll_retries\": %lld, "
        "\"coll_reforms\": %lld, \"coll_desc_fallbacks\": %lld, "
        "\"stale_issued\": %lld, \"stale_ok\": %lld, "
        "\"stale_failed\": %lld, \"stale_executed\": %lld, "
        "\"expired_probes\": %lld, "
        "\"desc_issued\": %lld, \"desc_ok\": %lld, "
        "\"desc_failed\": %lld, \"desc_stale\": %lld, "
        "\"desc_rsp_issued\": %lld, \"desc_rsp_ok\": %lld, "
        "\"desc_rsp_resolves\": %lld, \"desc_rsp_sends\": %lld, "
        "\"verbs_issued\": %lld, \"verbs_ok\": %lld, "
        "\"verbs_failed\": %lld, \"verbs_stale\": %lld, "
        "\"verbs_regrants\": %lld, \"verbs_posted\": %lld, "
        "\"verbs_completed\": %lld, \"verbs_bytes\": %lld, "
        "\"verbs_stale_rejects\": %lld, \"verbs_windows\": %lld, "
        "\"verbs_pending\": %lld, \"coll_verb_steps\": %lld, "
        "\"coll_verb_fallbacks\": %lld, "
        "\"pool_pinned\": %lld, \"pool_reaped\": %lld, "
        "\"pool_peer_released\": %lld, \"epoch_rejects\": %lld, "
        "\"cost_admitted_milli\": %lld, \"cost_shed_milli\": %lld, "
        "\"overload_sheds\": %lld, "
        "\"outstanding\": %lld, \"reconnects\": %lld, "
        "\"reissues\": %lld, \"budget_exhausted\": %lld, "
        "\"drain_reroutes\": %lld, \"drain_notices\": %lld, "
        "\"goaways_sent\": %lld, "
        "\"zone\": \"%s\", \"zone_spills\": %lld, "
        "\"zone_local_picks\": %lld, \"zone_partition_cuts\": %lld, "
        "\"dcn_out_bytes\": %lld, \"dcn_in_bytes\": %lld, "
        "\"stream_open\": %lld, \"stream_resumed\": %lld, "
        "\"stream_replayed\": %lld, \"stream_credit_stalls\": %lld, "
        "\"stream_aborts\": %lld, \"stream_ring_hw\": %lld}\n",
        id, port, (long long)c.lb_issued.load(), (long long)c.lb_ok.load(),
        (long long)c.lb_failed.load(), (long long)c.shm_issued.load(),
        (long long)c.shm_ok.load(), (long long)c.shm_failed.load(),
        (long long)c.coll_issued.load(), (long long)c.coll_ok.load(),
        (long long)c.coll_failed.load(),
        (long long)c.coll_verify_failed.load(),
        (long long)c.coll_nranks_last.load(),
        (long long)VarInt("rpc_collective_ops"),
        (long long)VarInt("rpc_collective_steps"),
        (long long)VarInt("rpc_collective_retries"),
        (long long)VarInt("rpc_collective_reforms"),
        (long long)VarInt("rpc_collective_desc_fallbacks"),
        (long long)c.stale_issued.load(), (long long)c.stale_ok.load(),
        (long long)c.stale_failed.load(),
        (long long)g_stale_executed.load(),
        (long long)c.expired_probes.load(),
        (long long)c.desc_issued.load(), (long long)c.desc_ok.load(),
        (long long)c.desc_failed.load(), (long long)c.desc_stale.load(),
        (long long)c.desc_rsp_issued.load(),
        (long long)c.desc_rsp_ok.load(),
        (long long)VarInt("rpc_pool_desc_rsp_resolves"),
        (long long)VarInt("rpc_pool_desc_rsp_sends"),
        (long long)c.verbs_issued.load(), (long long)c.verbs_ok.load(),
        (long long)c.verbs_failed.load(),
        (long long)c.verbs_stale.load(),
        (long long)c.verbs_regrants.load(),
        (long long)verbs::posted(), (long long)verbs::completed(),
        (long long)verbs::bytes_moved(),
        (long long)verbs::stale_rejects(),
        (long long)verbs::window_count(),
        (long long)verbs::pending_posts(),
        (long long)VarInt("rpc_collective_verb_steps"),
        (long long)VarInt("rpc_collective_verb_fallbacks"),
        (long long)block_lease::pinned(),
        (long long)block_lease::expired_reaped(),
        (long long)block_lease::peer_released(),
        (long long)VarInt("rpc_pool_epoch_rejects"),
        (long long)VarInt("rpc_server_cost_admitted"),
        (long long)VarInt("rpc_server_cost_shed"),
        (long long)VarInt("rpc_server_overload_sheds"),
        (long long)c.outstanding.load(), (long long)c.reconnects.load(),
        reissues, (long long)VarInt("rpc_retry_budget_exhausted"),
        (long long)VarInt("rpc_client_drain_reroutes"),
        (long long)VarInt("rpc_client_drain_notices"),
        (long long)VarInt("rpc_server_drain_goaways_sent"),
        g_my_zone.c_str(), (long long)VarInt("rpc_lb_zone_spills"),
        (long long)VarInt("rpc_lb_zone_local_picks"),
        (long long)FaultInjection::zone_partition_cuts(),
        (long long)transport_stats::out_bytes(TierDcn()),
        (long long)transport_stats::in_bytes(TierDcn()),
        (long long)push_stream::Opens(), (long long)push_stream::Resumed(),
        (long long)push_stream::ReplayedChunks(),
        (long long)push_stream::CreditStalls(),
        (long long)push_stream::Aborts(),
        (long long)push_stream::RingHighwater());
    fflush(stdout);
}

// SIGTERM/SIGUSR2 watcher (the -graceful_quit_on_sigterm wiring): a
// plain fiber polling the signal flags — never shutdown work in signal
// context. SIGUSR2 = drain-only (announce + keep serving, so operators
// can watch /status flip to draining: 1); SIGTERM = the zero-downtime
// exit used by the rolling-restart soak:
//   announce -> serve through the drain window (peers steer away) ->
//   stop own client traffic -> GracefulStop -> REPORT -> _exit(0).
struct QuitWatchArgs {
    Server* server;
    NodeState* st;
    int id;
    int port;
    int drain_ms;
};

void* GracefulQuitWatcher(void* arg) {
    std::unique_ptr<QuitWatchArgs> a((QuitWatchArgs*)arg);
    bool announced = false;
    while (!IsAskedToQuit()) {
        if (a->st->watcher_stop.load(std::memory_order_acquire)) {
            return nullptr;  // main() is tearing down; our pointers die
        }
        if (!announced && IsAskedToDrain()) {
            a->server->StartDraining();
            announced = true;
            printf("DRAINING\n");
            fflush(stdout);
        }
        fiber_usleep(20 * 1000);
    }
    a->server->StartDraining();
    if (!announced) {
        printf("DRAINING\n");
        fflush(stdout);
    }
    fiber_usleep((int64_t)a->drain_ms * 1000);
    if (g_coll_engine != nullptr) g_coll_engine->Shutdown();
    a->st->StopTraffic();  // our own in-flight client calls complete
    a->server->GracefulStop(2000);
    PrintReport(a->id, a->port, a->st->counters);
    fflush(nullptr);
    _exit(0);
    return nullptr;
}

// Unclean-exit black box: dump the flight rings to --blackbox before
// bailing with an error (the crash handler only covers signal deaths).
int FailExit(int code) {
    flight::DumpToConfiguredPath();
    return code;
}

}  // namespace

int main(int argc, char** argv) {
    prctl(PR_SET_PDEATHSIG, SIGKILL);  // die with the driving pytest
    int port = 0, id = 0;
    int timeout_cl_ms = 0;
    int drain_ms = 1200;
    const char* blackbox_path = nullptr;
    bool lb_only = false;
    bool inline_echo = false;
    bool desc_traffic = false;
    bool verbs_traffic = false;
    bool collective = false;
    bool coll_traffic = false;
    bool coll_verbs = false;
    const char* peers_file = nullptr;
    const char* dcn_peers_file = nullptr;
    for (int i = 1; i < argc; ++i) {
        if (strcmp(argv[i], "--port") == 0 && i + 1 < argc) {
            port = atoi(argv[++i]);
        } else if (strcmp(argv[i], "--id") == 0 && i + 1 < argc) {
            id = atoi(argv[++i]);
        } else if (strcmp(argv[i], "--peers") == 0 && i + 1 < argc) {
            peers_file = argv[++i];
        } else if (strcmp(argv[i], "--zone") == 0 && i + 1 < argc) {
            // Pod identity (ISSUE 14): feeds -rpc_zone (zone-aware LB +
            // dcn-tier naming sockets) and the collective membership.
            g_my_zone = argv[++i];
            SetFlagValue("rpc_zone", g_my_zone);
        } else if (strcmp(argv[i], "--dcn_peers") == 0 && i + 1 < argc) {
            // Cross-pod peers (naming-line format, "ip:port zone=B"):
            // linked over pinned dcn-tier channels instead of shm.
            dcn_peers_file = argv[++i];
        } else if (strcmp(argv[i], "--timeout_cl_ms") == 0 && i + 1 < argc) {
            timeout_cl_ms = atoi(argv[++i]);
        } else if (strcmp(argv[i], "--tenant") == 0 && i + 1 < argc) {
            g_tenant = argv[++i];
        } else if (strcmp(argv[i], "--priority") == 0 && i + 1 < argc) {
            g_priority.store(atoi(argv[++i]), std::memory_order_relaxed);
        } else if (strcmp(argv[i], "--drain_ms") == 0 && i + 1 < argc) {
            // SIGTERM grace window: announce, then keep serving this long
            // before the final GracefulStop (rolling restarts observe
            // /status draining:1 during it).
            drain_ms = atoi(argv[++i]);
        } else if (strcmp(argv[i], "--stream_token_delay_us") == 0 &&
                   i + 1 < argc) {
            g_stream_token_delay_us.store(atoll(argv[++i]),
                                          std::memory_order_relaxed);
        } else if (strcmp(argv[i], "--traffic_delay_ms") == 0 &&
                   i + 1 < argc) {
            g_traffic_delay_ms.store(atoi(argv[++i]),
                                     std::memory_order_relaxed);
        } else if (strcmp(argv[i], "--inline_echo") == 0) {
            // Run-to-completion soak mode (ISSUE 7): flag the echo
            // method inline-safe so small requests run on the input
            // fiber. OFF by default — this node's handler can be told to
            // sleep ("delay") and to chain downstream calls, both of
            // which violate the inline-safe contract; the delay command
            // clears the flag for its phase.
            inline_echo = true;
        } else if (strcmp(argv[i], "--desc_traffic") == 0) {
            // Pool chaos soak mode (ISSUE 10): drive one-sided
            // descriptor traffic (pinned pool blocks) over the shm
            // links so kills/chaos hit the zero-copy data path.
            desc_traffic = true;
        } else if (strcmp(argv[i], "--verbs_traffic") == 0) {
            // Verb chaos soak mode (ISSUE 18): drive one-sided
            // REMOTE_WRITE/REMOTE_READ round-trips against leased peer
            // windows so kills/chaos hit the verb plane.
            verbs_traffic = true;
        } else if (strcmp(argv[i], "--coll_verbs") == 0) {
            // Collective rounds default to the verbs-backed step
            // exchange (one SGL verb + doorbell per ring step).
            coll_verbs = true;
        } else if (strcmp(argv[i], "--collective") == 0) {
            // Mesh collectives (ISSUE 13): serve the CollectiveService
            // + engine; rounds are driven by stdin "coll ..." commands
            // (tests/test_collectives.py) or the --coll_traffic fiber
            // (the soak).
            collective = true;
        } else if (strcmp(argv[i], "--coll_traffic") == 0) {
            collective = true;
            coll_traffic = true;
        } else if (strcmp(argv[i], "--lb_only") == 0) {
            // Rolling-restart soak mode: only the naming/LB plane runs.
            // The shm-ICI links die hard when a peer exits (no drain
            // protocol on the queue pair yet) — the zero-failed-
            // completions invariant is an LB-plane contract.
            lb_only = true;
        } else if (strcmp(argv[i], "--blackbox") == 0 && i + 1 < argc) {
            // Flight-recorder black box (ISSUE 19): install the fatal-
            // signal dump handler writing to this path, and dump there
            // on unclean (non-signal) exits too.
            blackbox_path = argv[++i];
        } else if (strcmp(argv[i], "--flag") == 0 && i + 1 < argc) {
            // --flag name=value: soak-tuned knobs (breaker windows,
            // health-check cadence, ...) without bespoke plumbing.
            std::string kv = argv[++i];
            const size_t eq = kv.find('=');
            if (eq == std::string::npos ||
                !SetFlagValue(kv.substr(0, eq), kv.substr(eq + 1))) {
                fprintf(stderr, "bad --flag %s\n", kv.c_str());
                return 2;
            }
        }
    }
    if (port <= 0 || peers_file == nullptr) {
        fprintf(stderr,
                "usage: mesh_node --port N --peers FILE [--id K] "
                "[--zone NAME] [--dcn_peers FILE] "
                "[--lb_only] [--inline_echo] [--desc_traffic] "
                "[--verbs_traffic] "
                "[--collective] [--coll_traffic] [--coll_verbs] "
                "[--drain_ms N] "
                "[--timeout_cl_ms N] [--tenant NAME] [--priority 0..7] "
                "[--blackbox PATH] [--flag name=value]...\n"
                "  with --flag graceful_quit_on_sigterm=true: SIGTERM "
                "drains gracefully and exits 0; SIGUSR2 drains without "
                "quitting\n");
        return 2;
    }
    // Node identity stamps every dump (blackbox_merge.py keys timelines
    // on it); the crash handler is installed only when a path was given.
    {
        char nn[32];
        snprintf(nn, sizeof(nn), "node%d:%d", id, port);
        flight::SetNodeName(nn);
    }
    if (blackbox_path != nullptr) {
        flight::InstallCrashHandler(blackbox_path);
    }
    if (IciBlockPool::Init() != 0) {
        fprintf(stderr, "IciBlockPool::Init failed\n");
        return FailExit(1);
    }

    g_my_port = port;
    static EchoServiceImpl service;
    static CollectiveServiceImpl coll_service;
    static Server server;
    if (server.AddService(&service) != 0) return FailExit(1);
    if (collective && server.AddService(&coll_service) != 0) {
        return FailExit(1);
    }
    if (inline_echo) {
        server.SetMethodInlineSafe("benchpb.EchoService", "Echo");
    }
    EndPoint listen;
    str2endpoint("127.0.0.1", port, &listen);
    ServerOptions sopts;
    if (timeout_cl_ms > 0) {
        // Budget-aware admission: requests whose propagated remaining
        // deadline is below the observed service time are shed cheaply.
        sopts.timeout_concurrency = true;
        sopts.timeout_cl_options.timeout_ms = timeout_cl_ms;
    }
    if (server.Start(listen, timeout_cl_ms > 0 ? &sopts : nullptr) != 0) {
        fprintf(stderr, "listen failed on port %d\n", port);
        return FailExit(1);
    }

    static NodeState st;
    // Naming-service membership: the rr LB channel resolves the same
    // file every node shares; its sockets carry circuit breakers and
    // health checks (FLAGS_ns_health_check_interval_ms).
    st.lb_channel.reset(new Channel);
    ChannelOptions lopts;
    lopts.timeout_ms = 800;
    lopts.max_retry = 2;
    const std::string url = std::string("file://") + peers_file;
    if (st.lb_channel->Init(url.c_str(), "rr", &lopts) != 0) {
        fprintf(stderr, "LB channel init failed for %s\n", url.c_str());
        return FailExit(1);
    }
    // Mesh links: one shm channel per same-zone peer (self excluded;
    // cross-zone entries in the naming file belong to the OTHER pod and
    // are reached through --dcn_peers links, never shm). Peer zones are
    // registered with the fault-injection layer so one
    // chaos_partition_zone command can cut a whole pod.
    if (!lb_only) {
        FILE* f = fopen(peers_file, "r");
        if (f == nullptr) return FailExit(1);
        char line[128];
        while (fgets(line, sizeof(line), f) != nullptr) {
            NSNode node;
            if (ParseNamingLine(line, &node) != 0) continue;
            const std::string zone = ZoneFromTag(node.tag);
            if (!zone.empty()) {
                FaultInjection::SetPeerZone(node.ep, zone);
            }
            if (node.ep.port == port) continue;  // self
            if (!zone.empty() && zone != g_my_zone) continue;  // other pod
            auto link = std::make_unique<PeerLink>();
            link->ep = node.ep;
            link->zone = g_my_zone;
            st.links.push_back(std::move(link));
        }
        fclose(f);
        if (dcn_peers_file != nullptr) {
            FILE* df = fopen(dcn_peers_file, "r");
            if (df == nullptr) return FailExit(1);
            while (fgets(line, sizeof(line), df) != nullptr) {
                NSNode node;
                if (ParseNamingLine(line, &node) != 0) continue;
                if (node.ep.port == port) continue;
                auto link = std::make_unique<PeerLink>();
                link->ep = node.ep;
                link->dcn = true;
                link->zone = ZoneFromTag(node.tag);
                FaultInjection::SetPeerZone(node.ep, link->zone);
                st.links.push_back(std::move(link));
            }
            fclose(df);
        }
    }

    // Collective engine over the shm-link mesh (needs st.links).
    static std::unique_ptr<MeshMembership> coll_membership;
    static BenchpbCollCodec coll_codec;
    static std::unique_ptr<CollectiveEngine> coll_engine;
    if (collective && !lb_only) {
        coll_membership.reset(new MeshMembership(&st));
        CollectiveOptions copts;
        copts.step_timeout_ms = 1500;
        copts.attempt_timeout_ms = 4000;
        copts.verbs_lane = coll_verbs;
        // Also bounds how long a rejoin-misaligned round can stall the
        // mesh before the straggler adopts the observed seq.
        copts.op_timeout_ms = 15000;
        coll_engine.reset(new CollectiveEngine(coll_membership.get(),
                                               &coll_codec, copts));
        g_coll_engine = coll_engine.get();
    }

    std::vector<fiber_t>& fibers = st.traffic_fibers;
    fiber_t tid;
    if (!lb_only &&
        fiber_start_background(&tid, nullptr, LinkMaintenanceFiber, &st) ==
            0) {
        fibers.push_back(tid);
    }
    if (coll_traffic && g_coll_engine != nullptr &&
        fiber_start_background(&tid, nullptr, CollTrafficFiber, &st) == 0) {
        fibers.push_back(tid);
    }
    if (fiber_start_background(&tid, nullptr, LbTrafficFiber, &st) == 0) {
        fibers.push_back(tid);
    }
    if (!lb_only) {
        if (fiber_start_background(&tid, nullptr, ShmTrafficFiber, &st) ==
            0) {
            fibers.push_back(tid);
        }
        if (desc_traffic &&
            fiber_start_background(&tid, nullptr, DescTrafficFiber, &st) ==
                0) {
            fibers.push_back(tid);
        }
        if (verbs_traffic &&
            fiber_start_background(&tid, nullptr, VerbsTrafficFiber,
                                   &st) == 0) {
            fibers.push_back(tid);
        }
        if (fiber_start_background(&tid, nullptr, StaleTrafficFiber, &st) ==
            0) {
            fibers.push_back(tid);
        }
        if (fiber_start_background(&tid, nullptr, ExpiredProbeFiber, &st) ==
            0) {
            fibers.push_back(tid);
        }
    }
    // Signal-driven zero-downtime lifecycle (active when the
    // -graceful_quit_on_sigterm flag installed the handlers at Start).
    fiber_t quit_watcher;
    bool have_quit_watcher = true;
    {
        auto* qa = new QuitWatchArgs{&server, &st, id, port, drain_ms};
        if (fiber_start_background(&quit_watcher, nullptr,
                                   GracefulQuitWatcher, qa) != 0) {
            delete qa;
            have_quit_watcher = false;
        }
    }

    printf("READY %d\n", port);
    fflush(stdout);

    // Control loop: "stop" -> quiesce traffic + report; "delay H S" ->
    // delay-heavy phase (handler sleeps H ms, stale fiber issues S-ms
    // budget calls; 0 0 = back to normal); "chain T ep..." -> one chained
    // echo under a T-ms deadline (prints CHAIN trace=<id>); EOF -> exit.
    char cmd[256];
    while (fgets(cmd, sizeof(cmd), stdin) != nullptr) {
        if (strncmp(cmd, "stop", 4) == 0) {
            st.StopTraffic();
            PrintReport(id, port, st.counters);
        } else if (strncmp(cmd, "report", 6) == 0) {
            PrintReport(id, port, st.counters);
        } else if (strncmp(cmd, "coll", 4) == 0 && cmd[4] == ' ') {
            // "coll <alg> <bytes> <seq>": run ONE collective round on a
            // fiber (the driver sends the same command to every node)
            // and print a COLL result line. alg: allreduce |
            // allreduce_serial | allgather | alltoall |
            // allreduce_verbs | allreduce_chunks (lane-pinned, ISSUE 18).
            char alg[32];
            unsigned long long cbytes = 0, cseq = 0;
            if (sscanf(cmd + 5, "%31s %llu %llu", alg, &cbytes, &cseq) ==
                3) {
                auto* a = new CollRunArgs;
                a->st = &st;
                a->alg = alg;
                a->bytes = cbytes;
                a->seq = cseq;
                a->print = true;
                fiber_t ct;
                if (fiber_start_background(&ct, nullptr, CollCommandFiber,
                                           a) != 0) {
                    CollCommandFiber(a);
                } else {
                    // Track it: teardown must join commanded rounds
                    // before the stack-local NodeState goes away (and
                    // before a REPORT claims outstanding == 0).
                    st.traffic_fibers.push_back(ct);
                }
            } else {
                printf("COLL {\"ok\": 0, \"error\": 22}\n");
                fflush(stdout);
            }
        } else if (strncmp(cmd, "chain", 5) == 0) {
            auto* a = new ChainArgs;
            char* save = nullptr;
            strtok_r(cmd, " \n", &save);  // "chain"
            char* tok = strtok_r(nullptr, " \n", &save);
            if (tok != nullptr) a->timeout_ms = atoll(tok);
            while ((tok = strtok_r(nullptr, " \n", &save)) != nullptr) {
                if (*tok != '\0') a->eps.push_back(tok);
            }
            fiber_t ct;
            if (fiber_start_background(&ct, nullptr, ChainCallFiber, a) !=
                0) {
                ChainCallFiber(a);
            }
        } else if (strncmp(cmd, "delay", 5) == 0) {
            int h = 0, s_ms = 0;
            if (sscanf(cmd + 5, "%d %d", &h, &s_ms) == 2) {
                // A sleeping handler must never run on the input fiber:
                // suspend run-to-completion for the delay phase.
                if (inline_echo) {
                    server.SetMethodInlineSafe("benchpb.EchoService",
                                               "Echo", h <= 0);
                }
                g_handler_delay_ms.store(h, std::memory_order_relaxed);
                g_stale_budget_ms.store(s_ms, std::memory_order_relaxed);
                printf("DELAY_OK %d %d\n", h, s_ms);
                fflush(stdout);
            }
        }
    }
    // EOF: orderly shutdown. Stop traffic if "stop" never arrived. The
    // quit watcher holds pointers to the stack-local server/state: stop
    // and join it FIRST. (If a SIGTERM raced us, the join blocks until
    // the watcher's own GracefulStop path _exits the process — also
    // orderly.)
    if (have_quit_watcher) {
        st.watcher_stop.store(true, std::memory_order_release);
        fiber_join(quit_watcher, nullptr);
    }
    // Unpark collective drivers/handlers BEFORE joining the traffic
    // fibers (a commanded round blocked in a fan-out would otherwise
    // hold the join for its op timeout) and before Join (a handler
    // fiber parked in the engine would hold its connection open).
    if (g_coll_engine != nullptr) g_coll_engine->Shutdown();
    st.StopTraffic();
    server.Stop();
    server.Join();  // quiesces sockets: a leak would hang (pytest timeout)
    fflush(nullptr);
    _exit(0);  // skip static dtors (long-lived server discipline)
}
