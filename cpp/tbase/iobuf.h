// IOBuf: zero-copy, refcounted, non-contiguous buffer — THE payload type of
// the whole framework.
//
// Modeled on the reference's butil::IOBuf (src/butil/iobuf.h:62-84): an IOBuf
// is a tiny queue of BlockRefs over refcounted 8KB Blocks; append/cut move
// pointers, not bytes. The block allocator is pluggable
// (reference src/butil/iobuf.cpp:168 `blockmem_allocate`) which is how the
// RDMA transport takes over allocation so every block lives in registered
// memory (reference src/brpc/rdma/block_pool.h) — our ICI transport uses the
// same hook (cpp/tnet/block_pool.h).
//
// Thread-safety: a Block's refcount is atomic (blocks are shared across
// IOBufs and threads); an individual IOBuf object is NOT thread-safe, same
// contract as the reference.
#pragma once

#include <sys/uio.h>

#include <atomic>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <string>

namespace tpurpc {

class IOBuf {
public:
    static constexpr size_t DEFAULT_BLOCK_SIZE = 8192;  // incl. header
    static constexpr size_t DEFAULT_PAYLOAD = DEFAULT_BLOCK_SIZE - 32;

    // Pluggable block memory allocator (reference iobuf.cpp:168). The ICI
    // block pool installs its own pair so every IOBuf block is
    // transfer-registered memory.
    static void* (*blockmem_allocate)(size_t);
    static void (*blockmem_deallocate)(void*);
    // Optional cache veto: when set and returning true for a block's
    // memory, dec_ref bypasses the TLS/global block caches and frees
    // through blockmem_deallocate directly. The registered pool installs
    // one so SHARED-region blocks return to its peer-visible freelist
    // under cross-process pressure instead of migrating into per-thread
    // caches where AllocateSharedBlock can't reach them.
    static bool (*blockmem_cache_veto)(const void*);

    // Refcounted block. Lives in memory returned by blockmem_allocate; the
    // header is placed at the front, payload follows. Each block remembers
    // the deallocator that was current at creation, so swapping the
    // allocator pair mid-run (transport init) can never free a block with
    // the wrong deallocator.
    struct Block {
        std::atomic<int32_t> nshared;
        uint32_t size;  // bytes filled; append position shared by writers
        uint32_t cap;   // payload capacity
        Block* portal_next;       // TLS cache list linkage
        void (*dealloc)(void*);   // deallocator captured at creation
        char data[0];

        void inc_ref() { nshared.fetch_add(1, std::memory_order_relaxed); }
        void dec_ref();
        bool full() const { return size >= cap; }
        uint32_t left_space() const { return cap - size; }
    };

    struct BlockRef {
        uint32_t offset;
        uint32_t length;
        Block* block;
    };

    IOBuf() { reset_small(); }
    IOBuf(const IOBuf& rhs);
    IOBuf(IOBuf&& rhs) noexcept;
    IOBuf& operator=(const IOBuf& rhs);
    IOBuf& operator=(IOBuf&& rhs) noexcept;
    ~IOBuf() { clear(); }

    size_t size() const { return nbytes_; }
    bool empty() const { return nbytes_ == 0; }
    void clear();
    void swap(IOBuf& other);

    // ---- appending (copies bytes into blocks) ----
    int append(const void* data, size_t count);
    int append(const char* cstr) { return append(cstr, strlen(cstr)); }
    int append(const std::string& s) { return append(s.data(), s.size()); }
    int push_back(char c) { return append(&c, 1); }

    // ---- appending by reference (zero-copy) ----
    void append(const IOBuf& other);
    void append(IOBuf&& other);
    // Append one BlockRef (takes one reference on ref.block).
    void append_ref(const BlockRef& ref);

    // ---- cutting (zero-copy ref moves) ----
    // Move at most n bytes from the front of *this to the back of *out.
    size_t cutn(IOBuf* out, size_t n);
    size_t cutn(void* out, size_t n);
    size_t cutn(std::string* out, size_t n);
    int cut1(char* c);
    size_t pop_front(size_t n);
    size_t pop_back(size_t n);

    // ---- reading without consuming ----
    size_t copy_to(void* buf, size_t n, size_t pos = 0) const;
    size_t copy_to(std::string* s, size_t n = (size_t)-1, size_t pos = 0) const;
    std::string to_string() const;
    // Stage [pos, pos + cap) of *this into dst[0..cap) in ONE pass: each
    // block is copied and folded into a crc32c by the same walk
    // (tbase/crc32c.h crc32c_copy_extend), and where *this ends before
    // pos + cap the rest of dst is zero-filled and folded in the same way.
    // Returns the crc32c of all of dst[0..cap); *copied (may be null) is
    // the bytes that came from *this. How a request attachment, or one
    // chunk of it, goes into a staging-ring slot (trpc/c_api.h).
    uint32_t copy_to_crc32c(void* dst, size_t cap, size_t pos,
                            size_t* copied) const;
    // Contiguous view of the first n bytes WITHOUT consuming: returns a
    // pointer into the first block when it already holds n contiguous
    // bytes (the common case — a readv lands whole headers in one block),
    // else copies them into `aux` (caller-provided, >= n bytes) and
    // returns aux. nullptr when size() < n. The zero-cut header peek of
    // protocol fast paths (reference butil::IOBuf::fetch).
    const void* fetch(void* aux, size_t n) const;
    // First byte, or -1 when empty.
    int front_byte() const;

    // ---- scatter-gather file I/O (reference iobuf.h:163-195) ----
    // writev() refs from the front; pops what was written. Returns bytes
    // written or -1 (errno set).
    ssize_t cut_into_file_descriptor(int fd, size_t size_hint = 1024 * 1024);
    // Multiple IOBufs in one writev (the KeepWrite batching path,
    // reference socket.cpp:1920 DoWrite).
    static ssize_t cut_multiple_into_file_descriptor(int fd, IOBuf* const* pieces,
                                                     size_t count);

    // ---- zero-copy block access (for transports) ----
    size_t backing_block_num() const { return nref_(); }
    // i-th ref's readable span. Valid until the IOBuf is mutated.
    const char* backing_block_data(size_t i, size_t* len) const;
    // Pop the front BlockRef, transferring its block reference to *out
    // (the caller now owns one ref and must dec_ref it). How a transport
    // moves blocks into its send queue without touching refcounts. Returns
    // false when empty.
    bool cut_front_ref(BlockRef* out);

    // Equality by content (test convenience).
    bool equals(const std::string& s) const;

    // Create one block (exposed for IOPortal / appender).
    static Block* create_block(size_t block_size = DEFAULT_BLOCK_SIZE);
    // Thread-local block cache stats (tests).
    static size_t tls_cached_blocks();
    // Return this thread's cached blocks to their deallocators (a pool
    // allocator can then reuse them for region-constrained needs, e.g.
    // cross-process bounce buffers when the shared region ran dry).
    static void flush_tls_cache();

protected:
    friend class IOPortal;
    friend class IOBufAppender;

    // Representation: up to 2 inline refs (small view, covers most RPC
    // payloads: header + body), else a heap-allocated ring (big view) —
    // the same two-view scheme as reference iobuf.h:84.
    static constexpr uint32_t kInlineRefs = 2;

    struct BigView {
        uint32_t start;
        uint32_t count;
        uint32_t cap;
        BlockRef* refs;
    };

    bool is_small() const { return !is_big_; }
    uint32_t nref_() const { return is_big_ ? big_.count : small_count_; }
    BlockRef& ref_at(uint32_t i) {
        return is_big_ ? big_.refs[(big_.start + i) % big_.cap] : small_[i];
    }
    const BlockRef& ref_at(uint32_t i) const {
        return is_big_ ? big_.refs[(big_.start + i) % big_.cap] : small_[i];
    }
    void push_back_ref_(const BlockRef& r);  // no refcount change
    void pop_front_ref_();                   // releases ref
    void pop_back_ref_();                    // releases ref
    void reset_small() {
        is_big_ = false;
        small_count_ = 0;
        nbytes_ = 0;
    }

    union {
        BlockRef small_[kInlineRefs];
        BigView big_;
    };
    uint32_t small_count_;
    bool is_big_;
    size_t nbytes_;
};

// IOPortal: an IOBuf that can read from a file descriptor, keeping a list of
// partially-filled blocks to append into (reference iobuf.h IOPortal).
class IOPortal : public IOBuf {
public:
    IOPortal() : block_(nullptr) {}
    ~IOPortal();
    // readv() up to max_count bytes into blocks appended to *this.
    // Returns bytes read, 0 on EOF, -1 on error.
    ssize_t append_from_file_descriptor(int fd, size_t max_count = 65536);
    void return_cached_blocks();

private:
    Block* block_;  // current partially-filled block
};

// Appender with a cached write pointer (reference IOBufAppender).
class IOBufAppender {
public:
    explicit IOBufAppender(IOBuf* buf) : buf_(buf) {}
    int append(const void* data, size_t n) { return buf_->append(data, n); }
    int push_back(char c) { return buf_->push_back(c); }
    IOBuf* buf() { return buf_; }

private:
    IOBuf* buf_;
};

}  // namespace tpurpc
