// C ABI surface of the framework: the pieces the Python/JAX side drives
// directly (ctypes over libtpurpc.so), so the multi-chip dryrun and the
// device-path benchmark exercise the FRAMEWORK's bytes — real tpu_std
// framing (policy_tpu_std.cc), real crc32c (tbase/crc32c.cc), staging
// buffers from the registered-memory ICI block pool (tici/block_pool.cc)
// — instead of a Python re-implementation.
//
// Reference parity: this plays the role the RDMA-registered IOBuf
// allocator plays in /root/reference/src/brpc/rdma/block_pool.h — the
// transport pool hands out the memory payloads are framed into, and the
// device DMA (jax.device_put on this side, ibv_post_send there) reads
// straight from it.
#pragma once

#include <stddef.h>
#include <stdint.h>

extern "C" {

// One-time framework init (protocol registry + ICI block pool). Returns 0.
int tpurpc_global_init();

// The framework's crc32c (RFC 3720 polynomial; tbase/crc32c.h).
uint32_t tpurpc_crc32c(uint32_t init, const void* data, size_t n);
// memcpy(dst, src, n) that returns tpurpc_crc32c(init, src, n): one read
// of src, one write of dst (tbase/crc32c.h crc32c_copy_extend; no
// overlap). How a payload is staged into a ring slot: the crc goes to
// tpurpc_frame_in_place, which then never reads the payload.
// rpc_stage_fused_bytes += n.
uint32_t tpurpc_crc32c_copy(uint32_t init, void* dst, const void* src,
                            size_t n);
// The same through the table path whatever the cpu, uncounted, for the
// tests that hold the two paths to each other (dst NULL: checksum only).
uint32_t tpurpc_crc32c_copy_tables(uint32_t init, void* dst, const void* src,
                                   size_t n);
// /vars rpc_stage_fused_bytes: bytes staged with their crc32c in one pass
// (tpurpc_crc32c_copy, tpurpc_call_copy_out).
long tpurpc_stage_fused_bytes();

// Registered-memory staging buffers from the ICI block pool. Allocation
// routes through the slab-class allocator (recyclable; ISSUE 9c) for
// class-sized requests and falls back to carve-only registered chunks
// above the largest class.
void* tpurpc_block_alloc(size_t n);
void tpurpc_block_free(void* p);
// 1 if p lies inside the registered region (diagnostic for tests).
int tpurpc_block_is_registered(const void* p);

// Slab-class allocator stats (zero-copy / recycle proof for tests).
long tpurpc_slab_allocated();
long tpurpc_slab_recycled();
// Identity of this process's shared pool (the pool_id of one-sided
// descriptors); 0 when the pool is anonymous.
uint64_t tpurpc_pool_id();

// ---- device staging ring (ISSUE 9a) ----
// A depth-N ring of registered staging slots for the pipelined device
// data path (see tici/block_pool.h DeviceStagingRing). Acquire hands
// out slots in FIFO order, blocking up to timeout_us (<0 = forever)
// while all slots are in flight; Complete releases them (out-of-order
// completes are held until the predecessors finish).
// Acquire returns the slot index, -1 on timeout, -2 once the ring is
// aborted (poisoned): a device-stream error must unblock parked Python
// threads instead of wedging them forever (ISSUE 10c).
void* tpurpc_ring_create(uint32_t depth, size_t slot_bytes);
void tpurpc_ring_destroy(void* ring);
int tpurpc_ring_acquire(void* ring, long timeout_us);
int tpurpc_ring_complete(void* ring, uint32_t slot);
// Poison the ring: every parked and future acquire returns -2.
void tpurpc_ring_abort(void* ring);
int tpurpc_ring_aborted(void* ring);
void* tpurpc_ring_slot(void* ring, uint32_t slot);
size_t tpurpc_ring_slot_bytes(void* ring);
uint32_t tpurpc_ring_depth(void* ring);
int tpurpc_ring_registered(void* ring);
uint64_t tpurpc_ring_inflight_highwater(void* ring);

// The stage clock's table (tvar/stage_recorder.h) as JSON; returns its
// length, copied into `out` only when it fits in `cap` with its NUL.
long tpurpc_stage_dump(char* out, size_t cap);

// ---- block leases (ISSUE 10a) ----
// Crash-safety counters of the pinned-block lease registry
// (tici/block_lease.h): live pins, expiry-reaped pins, and the local
// pool's current epoch — the leak/staleness evidence the device-ring
// tests read.
uint64_t tpurpc_lease_pinned();
uint64_t tpurpc_lease_reaped();
uint64_t tpurpc_pool_epoch();

// ---- one-sided verbs (ISSUE 18) ----
// Counters of the verb plane (tici/verbs.h): posted/completed verbs,
// bytes moved by REMOTE_READ/REMOTE_WRITE, stale-epoch rejects, and CQ
// parks — plus the live window / pending-post gauges the soak uses as
// leak evidence (a healthy run ends with both at 0).
long tpurpc_verbs_posted();
long tpurpc_verbs_completed();
long tpurpc_verbs_bytes();
long tpurpc_verbs_stale_rejects();
long tpurpc_verbs_cq_parks();
long tpurpc_verbs_windows();
long tpurpc_verbs_pending();

// ---- transport tier registry (ISSUE 12) ----
// Introspection of the first-class Transport seam (tnet/transport.h):
// how many endpoint types are registered, their names, and their
// capabilities — so the Python side can assert the uniform tier story
// (tcp/ici/shm_xproc/device) without parsing a portal page.
int tpurpc_transport_tier_count();
// Copies the tier's name into out[0..cap) (NUL-terminated, truncated to
// cap-1). Returns the name length, or -1 for a bad tier id.
long tpurpc_transport_tier_name(int tier, char* out, size_t cap);
// 1/0 capability bits; -1 for a bad tier id.
int tpurpc_transport_tier_descriptor_capable(int tier);
int tpurpc_transport_tier_zero_copy(int tier);
int tpurpc_transport_tier_cross_process(int tier);
// One-sided verb plane (ISSUE 18): does the tier take REMOTE_READ /
// REMOTE_WRITE against leased pool windows, and how many scatter-gather
// entries fit in one verb (0 = one-sided-incapable).
int tpurpc_transport_tier_one_sided(int tier);
long tpurpc_transport_tier_sgl_max(int tier);
// Per-tier attribution counters (ops for the device tier's staging-ring
// completes; bytes for socket-attached tiers).
long tpurpc_transport_tier_ops(int tier);

// ---- pull server and blocking client (ISSUE 29, 33) ----
// The process that holds the chip is the Python/JAX one, so a replica is a
// Server INSIDE that process. It hosts two services, both tpu_std, on the
// listener `echo_bench --ici-server` opens (TCP handshake, then the shm
// queue pair; builtin portal on the same port):
//   tensorpb.Tensor/Step  attachment in, attachment out (tensor.proto);
//   kvpb.Cache/Put, /Get  a layer of a prompt's cache into, and out of, a
//                         pool that stays on the device (kvcache.proto).
// Every method's handler only stamps and PARKS the call and returns: no
// fiber worker ever runs the caller's code or waits for its interpreter
// lock. The process pulls parked calls of all methods, in the order they
// arrived, with tpurpc_server_take and answers each from whichever thread
// it likes.
//
// Stages (tvar/stage_recorder.h), both inside/around trpc.handler:
// tdev.take_wait = handler entered -> taken; tdev.reply = reply entered ->
// reply enqueued on the socket. /vars: rpc_tensor_calls (steps whose result
// the device gave back: tpurpc_tensor_step_answered), rpc_tensor_bytes_in,
// rpc_tensor_failed, rpc_tensor_parked_highwater (calls of any method);
// rpc_kv_puts, rpc_kv_gets (answered without error), rpc_kv_failed,
// rpc_kv_chunks, rpc_kv_bytes_landed (tpurpc_kv_chunk_landed),
// rpc_kv_get_chunks, rpc_kv_bytes_read (tpurpc_kv_chunk_read),
// rpc_kv_get_misses,
// rpc_kv_evictions, rpc_kv_pool_bytes, rpc_kv_resident_bytes
// (tpurpc_kv_pool_state).
//
// Set one of the framework's flags (tbase/flags.h; what /flags lists) by
// name, e.g. socket_send_buffer_size: the embedding process's to choose,
// before it starts a server or opens a channel. 0, or -1 where the flag
// is unknown or refuses the value.
int tpurpc_flag_set(const char* name, const char* value);
// Starts the server on 127.0.0.1:`port` (0 = ephemeral). NULL on failure.
void* tpurpc_server_start(int port);
int tpurpc_server_port(void* server);
// The method of a parked call, as tpurpc_server_take reports it.
enum { TPURPC_METHOD_STEP = 0, TPURPC_METHOD_PUT = 1, TPURPC_METHOD_GET = 2 };
// Blocks up to timeout_us (<0 = until a call or the close) for a parked
// call. Returns its handle, sets *len to the request attachment's length
// and, where `what` is not NULL, what[0] to the call's method and what[1],
// what[2] to the request's integer fields (Put, Get: session, layer; Step:
// 0, 0); NULL with *status 0 on timeout, -2 once the queue is closed.
void* tpurpc_server_take(void* server, long timeout_us, size_t* len,
                         int* status, uint64_t what[3]);
// Close the queue: every parked call fails with `code`, every later call
// fails on arrival, every parked and later take returns (-2). Calls
// already taken stay the taker's to answer. Idempotent.
void tpurpc_server_close_queue(void* server, int code);
// close_queue, then Server::GracefulStop (waits, bounded, until every
// taken call is answered and the write queues are flushed; then Stop +
// Join), then frees the server, or leaves that to the answer of the last
// taken call where one is still on its way.
void tpurpc_server_stop(void* server);
// Stage the request attachment from byte `offset` on into dst[0..cap):
// its blocks are walked once, each copied and folded into the crc in the
// same pass (IOBuf::copy_to_crc32c), and where the attachment ends before
// offset + cap what is left of dst is zero-filled and folded in the same
// way. Returns the attachment bytes copied and sets *crc_out (may be NULL)
// to the crc32c of all of dst[0..cap). rpc_stage_fused_bytes += cap.
long tpurpc_call_copy_out(void* call, size_t offset, void* dst, size_t cap,
                          uint32_t* crc_out);
// Answer: the response attachment is body ‖ tail (one copy each; tail may
// be NULL/0). Runs `done` on the calling thread and frees the handle.
int tpurpc_call_reply(void* call, const void* body, size_t n,
                      const void* tail, size_t tail_n);
// Answer with an attachment written in parts (ISSUE 35), from any thread
// but one at a time: begin makes it `n` bytes in parts of `part_bytes`
// (the last one shorter), each in the shared region where it can give the
// whole part -- one slab slot, else a chain of its general 8 KiB blocks --
// so the link posts it by reference and copies nothing (/vars
// rpc_reply_parts_shared), else one block of process memory, which the
// link bounce-copies (rpc_reply_parts_local). write copies src[0..len) to
// byte `offset` in one pass, across the part's blocks; a write stays
// inside one part (rpc_reply_part_writes += 1). commit answers the call with it, as
// tpurpc_call_reply does. 0, or -1 for a begin twice or a write out of
// bounds. A call failed after begin frees its parts.
int tpurpc_call_reply_begin(void* call, size_t n, size_t part_bytes);
int tpurpc_call_reply_write(void* call, size_t offset, const void* src,
                            size_t len);
int tpurpc_call_reply_commit(void* call);
// Answer a Put: the response's `word` and `admitted`, no attachment.
int tpurpc_call_reply_put(void* call, uint32_t word, uint64_t admitted);
// rpc_tensor_calls += 1. The service calls it where the D2H of a step's
// result came back, before it answers with it; an answer made any other
// way never passes here.
void tpurpc_tensor_step_answered(void);
// rpc_kv_chunks += 1, rpc_kv_bytes_landed += nbytes. The service calls it
// on the lane's completion thread, where the word of one chunk of a Put
// came back from the device; an acknowledgement made any other way never
// passes here.
void tpurpc_kv_chunk_landed(size_t nbytes);
// rpc_kv_get_chunks += 1, rpc_kv_bytes_read += nbytes. The service calls
// it on the lane's completion thread, where the bytes of one chunk of a Get
// came back from the device; a reply made any other way never passes here.
void tpurpc_kv_chunk_read(size_t nbytes);
// The device pool's gauges, and sessions evicted since the last call:
// rpc_kv_pool_bytes, rpc_kv_resident_bytes set, rpc_kv_evictions += evicted.
void tpurpc_kv_pool_state(long pool_bytes, long resident_bytes, long evicted);
// Fail the call with `code` (a TERR_*, an errno or any non-zero int) and
// frees the handle. A Get failed with ENOENT counts in rpc_kv_get_misses.
int tpurpc_call_fail(void* call, int code, const char* text);

// One blocking client of those services: `ici` != 0 pins the channel to
// the shm link (Channel::InitIci), else plain TCP. NULL on failure.
void* tpurpc_channel_open(const char* host, int port, int ici,
                          long timeout_ms);
// Step(attachment) -> attachment, synchronous, no retry. Returns 0 and
// sets *out_len (the reply's length; copied into out only up to cap), or
// the call's error code with its text in err[0..err_cap).
int tpurpc_channel_call(void* channel, const void* req, size_t n, void* out,
                        size_t cap, size_t* out_len, long timeout_ms,
                        char* err, size_t err_cap);
// Put(session, layer, attachment) -> *word, *admitted; and
// Get(session, layer) -> attachment (as tpurpc_channel_call's reply).
int tpurpc_channel_put(void* channel, uint64_t session, uint32_t layer,
                       const void* req, size_t n, uint32_t* word,
                       uint64_t* admitted, long timeout_ms, char* err,
                       size_t err_cap);
int tpurpc_channel_get(void* channel, uint64_t session, uint32_t layer,
                       void* out, size_t cap, size_t* out_len,
                       long timeout_ms, char* err, size_t err_cap);
void tpurpc_channel_close(void* channel);

// For tests only: hold up to `nbytes` of the shared region's blocks (the
// ones a link bounce-copies into), so that a send finds it full; returns
// the bytes held. A negative `nbytes` gives every held block back.
long tpurpc_region_hold(long nbytes);

// Frame `payload` as one tpu_std frame: "TRPC" header + RpcMeta
// {correlation_id, body_checksum=crc32c(payload)} + payload as raw
// attachment. Writes into out[0..out_cap) (`payload` may overlap it).
// Returns the frame size in bytes, or -1 if out_cap is too small.
long tpurpc_frame(uint64_t correlation_id, const void* payload, size_t n,
                  void* out, size_t out_cap);

// In-place framing for pool-resident payloads (ISSUE 9 satellite): the
// payload ALREADY lives at buf[payload_off .. payload_off+payload_len);
// the header + meta are written right-justified immediately before it,
// so the finished frame occupies buf[*frame_off .. payload_off+
// payload_len) with NO payload copy. payload_crc is the payload's
// crc32c, computed by the pass that staged it (tpurpc_crc32c_copy,
// tpurpc_call_copy_out), and is what the meta embeds: header + meta only,
// the payload is never read (ISSUE 30).
// Requires payload_off >= the header+meta size (~64 bytes is always
// enough). Returns the frame length and sets *frame_off; -1 when the
// prefix space is too small.
long tpurpc_frame_in_place(uint64_t correlation_id, void* buf,
                           size_t payload_off, size_t payload_len,
                           uint32_t payload_crc, size_t* frame_off);

// Parse ONE frame at buf[0..n): verifies the header, meta, and
// body_checksum. On success returns bytes consumed and sets *cid,
// *payload_off, *payload_len (payload bytes live at buf+*payload_off).
// Returns -1 if more bytes are needed, -2 if the frame is corrupt
// (bad magic/bounds/meta/checksum).
long tpurpc_unframe(const void* buf, size_t n, uint64_t* cid,
                    size_t* payload_off, size_t* payload_len);

}  // extern "C"
