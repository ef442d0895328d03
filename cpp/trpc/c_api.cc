#include "trpc/c_api.h"

#include <cstring>
#include <string>

#include "rpc_meta.pb.h"
#include "tbase/crc32c.h"
#include "tbase/iobuf.h"
#include "tici/block_lease.h"
#include "tici/block_pool.h"
#include "tici/verbs.h"
#include "tnet/transport.h"
#include "trpc/pb_compat.h"
#include "trpc/policy_tpu_std.h"
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
    // header/meta region about to be written). When the payload already
    // sits exactly at the frame's attachment position — staged in place
    // inside the destination pool buffer — the copy is skipped entirely:
    // the frame costs a header+meta write and the crc read only.
    if ((const char*)payload != att_pos) {
        memmove(att_pos, payload, n);
    }
    write_frame_header(o, meta_str.size(), n);
    memcpy(o + kHeaderLen, meta_str.data(), meta_str.size());
    return (long)frame_len;
}

long tpurpc_frame_in_place(uint64_t correlation_id, void* buf,
                           size_t payload_off, size_t payload_len,
                           size_t* frame_off, uint32_t* crc_out) {
    char* b = (char*)buf;
    const uint32_t crc =
        tpurpc::crc32c_extend(0, b + payload_off, payload_len);
    if (crc_out != nullptr) *crc_out = crc;
    std::string meta_str;
    if (!frame_meta(correlation_id, payload_len, crc, &meta_str)) {
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
