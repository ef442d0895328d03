#include "trpc/c_api.h"

#include <algorithm>
#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstring>
#include <deque>
#include <mutex>
#include <string>
#include <utility>

#include "kvcache.pb.h"
#include "rpc_meta.pb.h"
#include "tbase/crc32c.h"
#include "tbase/endpoint.h"
#include "tbase/errno.h"
#include "tbase/flags.h"
#include "tbase/iobuf.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tici/verbs.h"
#include "tensor.pb.h"
#include "tnet/transport.h"
#include "trpc/channel.h"
#include "trpc/controller.h"
#include "trpc/pb_compat.h"
#include "trpc/policy_tpu_std.h"
#include "trpc/server.h"
#include "tvar/reducer.h"
#include "tvar/stage_recorder.h"

namespace {
constexpr char kMagic[4] = {'T', 'R', 'P', 'C'};
constexpr size_t kHeaderLen = 12;  // "TRPC" + u32be body + u32be meta
}  // namespace

extern "C" {

int tpurpc_global_init() {
    tpurpc::GlobalInitializeOrDie();
    return tpurpc::IciBlockPool::Init() == 0 ? 0 : -1;
}

uint32_t tpurpc_crc32c(uint32_t init, const void* data, size_t n) {
    return tpurpc::crc32c_extend(init, (const char*)data, n);
}

namespace {
// What says the one-pass staging engaged (ISSUE 30): bytes that went into
// a staging buffer with their crc32c in one pass.
tpurpc::LazyAdder g_stage_fused_bytes("rpc_stage_fused_bytes");
}  // namespace

uint32_t tpurpc_crc32c_copy(uint32_t init, void* dst, const void* src,
                            size_t n) {
    *g_stage_fused_bytes << (int64_t)n;
    return tpurpc::crc32c_copy_extend(init, dst, src, n);
}

uint32_t tpurpc_crc32c_copy_tables(uint32_t init, void* dst, const void* src,
                                   size_t n) {
    return tpurpc::crc32c_copy_extend_tables(init, dst, src, n);
}

long tpurpc_stage_fused_bytes() {
    return (long)(*g_stage_fused_bytes).get_value();
}

void* tpurpc_block_alloc(size_t n) {
    if (tpurpc::IciBlockPool::initialized()) {
        // Slab classes first (recyclable registered slots); oversized
        // requests fall through to carve-only registered chunks inside
        // AllocateSlab.
        void* p = tpurpc::IciBlockPool::AllocateSlab(n);
        if (p != nullptr) return p;
    }
    return malloc(n);
}

void tpurpc_block_free(void* p) {
    if (tpurpc::IciBlockPool::Contains(p)) {
        // Slab slots recycle into their class freelist; carve-only
        // chunks are process-lifetime (FreeSlab ignores them).
        tpurpc::IciBlockPool::FreeSlab(p);
        return;
    }
    free(p);
}

int tpurpc_block_is_registered(const void* p) {
    return tpurpc::IciBlockPool::Contains(p) ? 1 : 0;
}

long tpurpc_slab_allocated() {
    return (long)tpurpc::IciBlockPool::slab_allocated();
}

long tpurpc_slab_recycled() {
    return (long)tpurpc::IciBlockPool::slab_recycled();
}

uint64_t tpurpc_pool_id() { return tpurpc::IciBlockPool::pool_id(); }

uint64_t tpurpc_pool_epoch() {
    return tpurpc::IciBlockPool::pool_epoch();
}

uint64_t tpurpc_lease_pinned() { return tpurpc::block_lease::pinned(); }

uint64_t tpurpc_lease_reaped() {
    return tpurpc::block_lease::expired_reaped() +
           tpurpc::block_lease::peer_released();
}

int tpurpc_transport_tier_count() {
    tpurpc::transport_stats::ExposeVars();  // built-ins registered
    return tpurpc::TransportTierCount();
}

long tpurpc_transport_tier_name(int tier, char* out, size_t cap) {
    const tpurpc::TransportTier* t = tpurpc::GetTransportTier(tier);
    if (t == nullptr || out == nullptr || cap == 0) return -1;
    const size_t n = strlen(t->name);
    const size_t ncopy = n < cap - 1 ? n : cap - 1;
    memcpy(out, t->name, ncopy);
    out[ncopy] = '\0';
    return (long)n;
}

int tpurpc_transport_tier_descriptor_capable(int tier) {
    const tpurpc::TransportTier* t = tpurpc::GetTransportTier(tier);
    return t != nullptr ? (t->descriptor_capable ? 1 : 0) : -1;
}

int tpurpc_transport_tier_zero_copy(int tier) {
    const tpurpc::TransportTier* t = tpurpc::GetTransportTier(tier);
    return t != nullptr ? (t->zero_copy ? 1 : 0) : -1;
}

int tpurpc_transport_tier_cross_process(int tier) {
    const tpurpc::TransportTier* t = tpurpc::GetTransportTier(tier);
    return t != nullptr ? (t->cross_process ? 1 : 0) : -1;
}

int tpurpc_transport_tier_one_sided(int tier) {
    const tpurpc::TransportTier* t = tpurpc::GetTransportTier(tier);
    return t != nullptr ? (t->one_sided ? 1 : 0) : -1;
}

long tpurpc_transport_tier_sgl_max(int tier) {
    const tpurpc::TransportTier* t = tpurpc::GetTransportTier(tier);
    return t != nullptr ? (long)t->sgl_max : -1;
}

long tpurpc_verbs_posted() { return (long)tpurpc::verbs::posted(); }

long tpurpc_verbs_completed() {
    return (long)tpurpc::verbs::completed();
}

long tpurpc_verbs_bytes() {
    return (long)tpurpc::verbs::bytes_moved();
}

long tpurpc_verbs_stale_rejects() {
    return (long)tpurpc::verbs::stale_rejects();
}

long tpurpc_verbs_cq_parks() { return (long)tpurpc::verbs::cq_parks(); }

long tpurpc_verbs_windows() {
    return (long)tpurpc::verbs::window_count();
}

long tpurpc_verbs_pending() {
    return (long)tpurpc::verbs::pending_posts();
}

long tpurpc_transport_tier_ops(int tier) {
    return (long)tpurpc::transport_stats::ops(tier);
}

void* tpurpc_ring_create(uint32_t depth, size_t slot_bytes) {
    return tpurpc::DeviceStagingRing::Create(depth, slot_bytes);
}

void tpurpc_ring_destroy(void* ring) {
    delete (tpurpc::DeviceStagingRing*)ring;
}

int tpurpc_ring_acquire(void* ring, long timeout_us) {
    return ((tpurpc::DeviceStagingRing*)ring)->Acquire(timeout_us);
}

int tpurpc_ring_complete(void* ring, uint32_t slot) {
    return ((tpurpc::DeviceStagingRing*)ring)->Complete(slot);
}

void tpurpc_ring_abort(void* ring) {
    ((tpurpc::DeviceStagingRing*)ring)->Abort();
}

int tpurpc_ring_aborted(void* ring) {
    return ((tpurpc::DeviceStagingRing*)ring)->aborted() ? 1 : 0;
}

void* tpurpc_ring_slot(void* ring, uint32_t slot) {
    return ((tpurpc::DeviceStagingRing*)ring)->slot(slot);
}

size_t tpurpc_ring_slot_bytes(void* ring) {
    return ((tpurpc::DeviceStagingRing*)ring)->slot_bytes();
}

uint32_t tpurpc_ring_depth(void* ring) {
    return ((tpurpc::DeviceStagingRing*)ring)->depth();
}

int tpurpc_ring_registered(void* ring) {
    return ((tpurpc::DeviceStagingRing*)ring)->registered() ? 1 : 0;
}

// The stage clock's table as the JSON object /status?format=json embeds
// under "stages" (tvar/stage_recorder.h), for a process with no portal.
// Returns the object's length; it is copied (NUL-terminated) only when it
// fits in `cap`, so a caller that got >= cap asks again with more room.
long tpurpc_stage_dump(char* out, size_t cap) {
    const std::string json = tpurpc::stage::DumpJson();
    if (out != nullptr && json.size() < cap) {
        memcpy(out, json.c_str(), json.size() + 1);
    }
    return (long)json.size();
}

uint64_t tpurpc_ring_inflight_highwater(void* ring) {
    return ((tpurpc::DeviceStagingRing*)ring)->inflight_highwater();
}

// ---- pull server and blocking client (ISSUE 29, 33) ----

namespace {

using tpurpc::stage::now_us;

tpurpc::LazyAdder g_tensor_calls("rpc_tensor_calls");
tpurpc::LazyAdder g_tensor_bytes_in("rpc_tensor_bytes_in");
tpurpc::LazyAdder g_tensor_failed("rpc_tensor_failed");
tpurpc::LazyAdder g_kv_puts("rpc_kv_puts");
tpurpc::LazyAdder g_kv_gets("rpc_kv_gets");
tpurpc::LazyAdder g_kv_failed("rpc_kv_failed");
tpurpc::LazyAdder g_kv_chunks("rpc_kv_chunks");
tpurpc::LazyAdder g_kv_bytes_landed("rpc_kv_bytes_landed");
tpurpc::LazyAdder g_kv_evictions("rpc_kv_evictions");
std::atomic<int64_t> g_parked_highwater{0};
std::atomic<int64_t> g_kv_pool_bytes{0};
std::atomic<int64_t> g_kv_resident_bytes{0};

int64_t ReadGauge(void* gauge) {
    return ((std::atomic<int64_t>*)gauge)->load(std::memory_order_relaxed);
}

// A call between its handler and its answer: what `done` needs, what the
// taker is told of it, and the stamp tdev.take_wait starts from.
class PullServer;
struct ParkedCall {
    tpurpc::Controller* cntl;
    google::protobuf::Closure* done;
    int64_t parked_us;
    PullServer* owner;  // told when a TAKEN call is answered
    int method;         // TPURPC_METHOD_*
    uint64_t session;   // Put, Get
    uint64_t layer;
    kvpb::PutResponse* put_response;  // Put
};

void FailCall(ParkedCall* call, int code, const char* text) {
    call->cntl->SetFailed(code, "%s", text);
    *(call->method == TPURPC_METHOD_STEP ? g_tensor_failed : g_kv_failed)
        << 1;
    call->done->Run();
    delete call;
}

// One queue of parked calls behind the handlers of both services: stamp,
// park, return.
class PullServer {
public:
    PullServer() {
        step_service.owner = this;
        cache_service.owner = this;
    }

    void Park(ParkedCall* call) {
        int closed_code = 0;
        {
            std::lock_guard<std::mutex> g(mu_);
            if (closed_code_ != 0) {
                closed_code = closed_code_;
            } else {
                parked_.push_back(call);
                const int64_t n = (int64_t)parked_.size();
                if (n > g_parked_highwater.load(std::memory_order_relaxed)) {
                    g_parked_highwater.store(n, std::memory_order_relaxed);
                }
            }
        }
        if (closed_code != 0) {
            FailCall(call, closed_code, "the service is closed");
            return;
        }
        cv_.notify_one();
    }

    ParkedCall* Take(long timeout_us, int* status) {
        std::unique_lock<std::mutex> lk(mu_);
        const auto ready = [this] {
            return closed_code_ != 0 || !parked_.empty();
        };
        if (timeout_us < 0) {
            cv_.wait(lk, ready);
        } else if (!cv_.wait_for(lk, std::chrono::microseconds(timeout_us),
                                 ready)) {
            *status = 0;
            return nullptr;
        }
        if (parked_.empty()) {  // closed: what was parked has been failed
            *status = -2;
            return nullptr;
        }
        ParkedCall* call = parked_.front();
        parked_.pop_front();
        ++taken_;
        return call;
    }

    // A taken call has been answered (reply or fail). True: that was the
    // last one of a server already stopped, and the caller frees it.
    bool Answered() {
        std::lock_guard<std::mutex> g(mu_);
        return --taken_ == 0 && stopped_;
    }

    // Stop + Join are over. True: no taken call is left that would call
    // Answered, and the caller frees the server; else the last one does.
    bool Stopped() {
        std::lock_guard<std::mutex> g(mu_);
        stopped_ = true;
        return taken_ == 0;
    }

    void CloseQueue(int code) {
        std::deque<ParkedCall*> orphans;
        {
            std::lock_guard<std::mutex> g(mu_);
            if (closed_code_ == 0) closed_code_ = code != 0 ? code : -1;
            orphans.swap(parked_);
        }
        cv_.notify_all();
        for (ParkedCall* call : orphans) {
            FailCall(call, closed_code_, "the service closed with the call "
                                         "parked");
        }
    }

    // tensorpb.Tensor/Step.
    struct StepService : public tensorpb::Tensor {
        PullServer* owner = nullptr;
        void Step(google::protobuf::RpcController* cntl_base,
                  const tensorpb::StepRequest* request,
                  tensorpb::StepResponse* response,
                  google::protobuf::Closure* done) override {
            auto* cntl = static_cast<tpurpc::Controller*>(cntl_base);
            response->set_send_ts_us(request->send_ts_us());
            *g_tensor_bytes_in << (int64_t)cntl->request_attachment().size();
            owner->Park(new ParkedCall{cntl, done, now_us(), owner,
                                       TPURPC_METHOD_STEP, 0, 0, nullptr});
        }
    };

    // kvpb.Cache/Put and /Get.
    struct CacheService : public kvpb::Cache {
        PullServer* owner = nullptr;
        void Put(google::protobuf::RpcController* cntl_base,
                 const kvpb::PutRequest* request, kvpb::PutResponse* response,
                 google::protobuf::Closure* done) override {
            owner->Park(new ParkedCall{
                static_cast<tpurpc::Controller*>(cntl_base), done, now_us(),
                owner, TPURPC_METHOD_PUT, request->session(),
                request->layer(), response});
        }
        void Get(google::protobuf::RpcController* cntl_base,
                 const kvpb::GetRequest* request, kvpb::GetResponse*,
                 google::protobuf::Closure* done) override {
            owner->Park(new ParkedCall{
                static_cast<tpurpc::Controller*>(cntl_base), done, now_us(),
                owner, TPURPC_METHOD_GET, request->session(),
                request->layer(), nullptr});
        }
    };

    tpurpc::Server server;
    StepService step_service;
    CacheService cache_service;

private:
    std::mutex mu_;
    std::condition_variable cv_;
    std::deque<ParkedCall*> parked_;
    int64_t taken_ = 0;    // taken and not answered yet
    int closed_code_ = 0;  // != 0: closed, and what new calls fail with
    bool stopped_ = false;  // tpurpc_server_stop is through with it
};

struct ClientChannel {
    tpurpc::Channel channel;
    tensorpb::Tensor_Stub stub{&channel};
    kvpb::Cache_Stub cache{&channel};
};

// `done` on the calling thread: trpc.handler ends, the reply is enqueued.
void Answer(ParkedCall* call, int64_t entered_us) {
    PullServer* owner = call->owner;
    call->done->Run();
    tpurpc::stage::Add(tpurpc::stage::kDevReply, now_us() - entered_us);
    delete call;
    if (owner->Answered()) delete owner;
}

// A client call's outcome: its error code with the text in err[0..err_cap),
// or 0 with the reply attachment's length in *out_len and as much of it as
// fits in out[0..cap).
int CallOutcome(tpurpc::Controller& cntl, char* err, size_t err_cap,
                void* out = nullptr, size_t cap = 0,
                size_t* out_len = nullptr) {
    if (cntl.Failed()) {
        if (err != nullptr && err_cap > 0) {
            snprintf(err, err_cap, "%s", cntl.ErrorText().c_str());
        }
        return cntl.ErrorCode() != 0 ? cntl.ErrorCode() : -1;
    }
    const tpurpc::IOBuf& att = cntl.response_attachment();
    if (out_len != nullptr) *out_len = att.size();
    if (out != nullptr && cap > 0) att.copy_to(out, cap);
    return 0;
}

}  // namespace

void* tpurpc_server_start(int port) {
    if (tpurpc_global_init() != 0) return nullptr;
    // In /vars from the first scrape, before the first call.
    for (tpurpc::LazyAdder* counter :
         {&g_tensor_calls, &g_tensor_bytes_in, &g_tensor_failed, &g_kv_puts,
          &g_kv_gets, &g_kv_failed, &g_kv_chunks, &g_kv_bytes_landed,
          &g_kv_evictions, &g_stage_fused_bytes}) {
        **counter << 0;
    }
    static const bool gauges = [] {
        const std::pair<const char*, std::atomic<int64_t>*> all[] = {
            {"rpc_tensor_parked_highwater", &g_parked_highwater},
            {"rpc_kv_pool_bytes", &g_kv_pool_bytes},
            {"rpc_kv_resident_bytes", &g_kv_resident_bytes}};
        for (const auto& gauge : all) {
            (new tpurpc::PassiveStatus<int64_t>(ReadGauge, gauge.second))
                ->expose(gauge.first);
        }
        return true;
    }();
    (void)gauges;
    auto* ps = new PullServer;
    tpurpc::EndPoint listen;
    tpurpc::str2endpoint("127.0.0.1", port, &listen);
    if (ps->server.AddService(&ps->step_service) != 0 ||
        ps->server.AddService(&ps->cache_service) != 0 ||
        ps->server.Start(listen, nullptr) != 0) {
        delete ps;
        return nullptr;
    }
    return ps;
}

int tpurpc_server_port(void* server) {
    return ((PullServer*)server)->server.listened_port();
}

void* tpurpc_server_take(void* server, long timeout_us, size_t* len,
                         int* status, uint64_t what[3]) {
    int st = 0;
    ParkedCall* call = ((PullServer*)server)->Take(timeout_us, &st);
    if (status != nullptr) *status = st;
    if (call == nullptr) return nullptr;
    tpurpc::stage::Add(tpurpc::stage::kTakeWait,
                       now_us() - call->parked_us);
    if (len != nullptr) *len = call->cntl->request_attachment().size();
    if (what != nullptr) {
        what[0] = (uint64_t)call->method;
        what[1] = call->session;
        what[2] = call->layer;
    }
    return call;
}

void tpurpc_server_close_queue(void* server, int code) {
    ((PullServer*)server)->CloseQueue(code);
}

void tpurpc_server_stop(void* server) {
    auto* ps = (PullServer*)server;
    ps->CloseQueue(tpurpc::TERR_CLOSE);
    // Stop closes the connections, and an answer not yet written would be
    // lost with them: the drain waits (bounded: a taker that never answers
    // must not hang it; Join then waits with the connection gone) until
    // every taken call is answered and the write queues are flushed.
    ps->server.GracefulStop(10 * 1000);
    // Join returns once every `done` has run; the thread that ran the last
    // one may still be on its way into Answered.
    if (ps->Stopped()) delete ps;
}

long tpurpc_call_copy_out(void* call, size_t offset, void* dst, size_t cap,
                          uint32_t* crc_out) {
    size_t copied = 0;
    const uint32_t crc =
        ((ParkedCall*)call)->cntl->request_attachment().copy_to_crc32c(
            dst, cap, offset, &copied);
    *g_stage_fused_bytes << (int64_t)cap;
    if (crc_out != nullptr) *crc_out = crc;
    return (long)copied;
}

int tpurpc_flag_set(const char* name, const char* value) {
    return tpurpc::SetFlagValue(name, value) ? 0 : -1;
}

void tpurpc_tensor_step_answered(void) { *g_tensor_calls << 1; }

void tpurpc_kv_chunk_landed(size_t nbytes) {
    *g_kv_chunks << 1;
    *g_kv_bytes_landed << (int64_t)nbytes;
}

void tpurpc_kv_pool_state(long pool_bytes, long resident_bytes,
                          long evicted) {
    g_kv_pool_bytes.store(pool_bytes, std::memory_order_relaxed);
    g_kv_resident_bytes.store(resident_bytes, std::memory_order_relaxed);
    *g_kv_evictions << (int64_t)evicted;
}

int tpurpc_call_reply(void* handle, const void* body, size_t n,
                      const void* tail, size_t tail_n) {
    auto* call = (ParkedCall*)handle;
    const int64_t entered_us = now_us();
    tpurpc::IOBuf& att = call->cntl->response_attachment();
    if (n > 0) att.append(body, n);
    if (tail_n > 0) att.append(tail, tail_n);
    if (call->method == TPURPC_METHOD_GET) *g_kv_gets << 1;
    Answer(call, entered_us);
    return 0;
}

int tpurpc_call_reply_put(void* handle, uint32_t word, uint64_t admitted) {
    auto* call = (ParkedCall*)handle;
    if (call->put_response == nullptr) return -1;  // not a Put
    const int64_t entered_us = now_us();
    call->put_response->set_word(word);
    call->put_response->set_admitted(admitted);
    *g_kv_puts << 1;
    Answer(call, entered_us);
    return 0;
}

int tpurpc_call_fail(void* handle, int code, const char* text) {
    PullServer* owner = ((ParkedCall*)handle)->owner;
    FailCall((ParkedCall*)handle, code != 0 ? code : -1,
             text != nullptr ? text : "failed");
    if (owner->Answered()) delete owner;
    return 0;
}

void* tpurpc_channel_open(const char* host, int port, int ici,
                          long timeout_ms) {
    if (tpurpc_global_init() != 0) return nullptr;
    tpurpc::EndPoint ep;
    if (tpurpc::str2endpoint(host, port, &ep) != 0) return nullptr;
    tpurpc::ChannelOptions opts;
    opts.timeout_ms = timeout_ms;
    opts.max_retry = 0;
    auto* cc = new ClientChannel;
    const int rc = ici ? cc->channel.InitIci(ep, &opts)
                       : cc->channel.Init(ep, &opts);
    if (rc != 0) {
        delete cc;
        return nullptr;
    }
    return cc;
}

int tpurpc_channel_call(void* channel, const void* req, size_t n, void* out,
                        size_t cap, size_t* out_len, long timeout_ms,
                        char* err, size_t err_cap) {
    auto* cc = (ClientChannel*)channel;
    tpurpc::Controller cntl;
    cntl.set_timeout_ms(timeout_ms);
    cntl.set_max_retry(0);
    tensorpb::StepRequest request;
    tensorpb::StepResponse response;
    request.set_send_ts_us(now_us());
    cntl.request_attachment().append(req, n);
    cc->stub.Step(&cntl, &request, &response, nullptr);
    return CallOutcome(cntl, err, err_cap, out, cap, out_len);
}

int tpurpc_channel_put(void* channel, uint64_t session, uint32_t layer,
                       const void* req, size_t n, uint32_t* word,
                       uint64_t* admitted, long timeout_ms, char* err,
                       size_t err_cap) {
    auto* cc = (ClientChannel*)channel;
    tpurpc::Controller cntl;
    cntl.set_timeout_ms(timeout_ms);
    cntl.set_max_retry(0);
    kvpb::PutRequest request;
    kvpb::PutResponse response;
    request.set_session(session);
    request.set_layer(layer);
    cntl.request_attachment().append(req, n);
    cc->cache.Put(&cntl, &request, &response, nullptr);
    if (word != nullptr) *word = response.word();
    if (admitted != nullptr) *admitted = response.admitted();
    return CallOutcome(cntl, err, err_cap);
}

int tpurpc_channel_get(void* channel, uint64_t session, uint32_t layer,
                       void* out, size_t cap, size_t* out_len,
                       long timeout_ms, char* err, size_t err_cap) {
    auto* cc = (ClientChannel*)channel;
    tpurpc::Controller cntl;
    cntl.set_timeout_ms(timeout_ms);
    cntl.set_max_retry(0);
    kvpb::GetRequest request;
    kvpb::GetResponse response;
    request.set_session(session);
    request.set_layer(layer);
    cc->cache.Get(&cntl, &request, &response, nullptr);
    return CallOutcome(cntl, err, err_cap, out, cap, out_len);
}

void tpurpc_channel_close(void* channel) { delete (ClientChannel*)channel; }

namespace {

// Serialize the one-frame meta for (cid, payload crc). Returns false on
// a serialization failure (can't happen for this fixed shape).
bool frame_meta(uint64_t cid, size_t n, uint32_t crc, std::string* out) {
    tpurpc::rpc::RpcMeta meta;
    meta.set_correlation_id(cid);
    meta.set_attachment_size((uint32_t)n);
    meta.set_body_checksum(crc);
    return meta.SerializeToString(out);
}

void write_frame_header(char* dst, size_t meta_size, size_t payload_len) {
    memcpy(dst, kMagic, 4);
    const uint32_t body = __builtin_bswap32((uint32_t)(meta_size +
                                                       payload_len));
    const uint32_t msz = __builtin_bswap32((uint32_t)meta_size);
    memcpy(dst + 4, &body, 4);
    memcpy(dst + 8, &msz, 4);
}

}  // namespace

long tpurpc_frame(uint64_t correlation_id, const void* payload, size_t n,
                  void* out, size_t out_cap) {
    std::string meta_str;
    if (!frame_meta(correlation_id, n,
                    tpurpc::crc32c_extend(0, (const char*)payload, n),
                    &meta_str)) {
        return -1;
    }
    const size_t frame_len = kHeaderLen + meta_str.size() + n;
    if (frame_len > out_cap) return -1;
    char* o = (char*)out;
    char* att_pos = o + kHeaderLen + meta_str.size();
    // Payload placement FIRST (memmove: the source may overlap the
    // header/meta region about to be written).
    memmove(att_pos, payload, n);
    write_frame_header(o, meta_str.size(), n);
    memcpy(o + kHeaderLen, meta_str.data(), meta_str.size());
    return (long)frame_len;
}

long tpurpc_frame_in_place(uint64_t correlation_id, void* buf,
                           size_t payload_off, size_t payload_len,
                           uint32_t payload_crc, size_t* frame_off) {
    char* b = (char*)buf;
    std::string meta_str;
    if (!frame_meta(correlation_id, payload_len, payload_crc, &meta_str)) {
        return -1;
    }
    const size_t prefix = kHeaderLen + meta_str.size();
    if (payload_off < prefix) return -1;  // not enough header room
    const size_t start = payload_off - prefix;
    write_frame_header(b + start, meta_str.size(), payload_len);
    memcpy(b + start + kHeaderLen, meta_str.data(), meta_str.size());
    if (frame_off != nullptr) *frame_off = start;
    return (long)(prefix + payload_len);
}

long tpurpc_unframe(const void* buf, size_t n, uint64_t* cid,
                    size_t* payload_off, size_t* payload_len) {
    const char* p = (const char*)buf;
    if (n < kHeaderLen) return -1;
    if (memcmp(p, kMagic, 4) != 0) return -2;
    uint32_t body_be, meta_be;
    memcpy(&body_be, p + 4, 4);
    memcpy(&meta_be, p + 8, 4);
    const uint32_t body_size = __builtin_bswap32(body_be);
    const uint32_t meta_size = __builtin_bswap32(meta_be);
    if (meta_size > body_size || body_size > (256u << 20)) return -2;
    if (n < kHeaderLen + body_size) return -1;
    tpurpc::rpc::RpcMeta meta;
    if (!meta.ParseFromArray(p + kHeaderLen, (int)meta_size)) return -2;
    const size_t off = kHeaderLen + meta_size;
    const size_t len = body_size - meta_size;
    if (meta.has_body_checksum() &&
        tpurpc::crc32c_extend(0, p + off, len) != meta.body_checksum()) {
        return -2;
    }
    if (cid != nullptr) *cid = meta.correlation_id();
    if (payload_off != nullptr) *payload_off = off;
    if (payload_len != nullptr) *payload_len = len;
    return (long)(kHeaderLen + body_size);
}

}  // extern "C"
