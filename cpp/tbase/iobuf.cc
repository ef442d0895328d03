#include "tbase/iobuf.h"

#include <errno.h>
#include <stdlib.h>
#include <unistd.h>

#include <algorithm>
#include <new>

#include "tbase/crc32c.h"
#include "tbase/flags.h"
#include "tbase/mpmc_queue.h"
#include "tbase/logging.h"

// 512 x 8KB = 4MB per thread: enough that a windowed stream of 1MB
// messages (128 blocks each) recycles through the cache instead of
// malloc/free + arena-trim churn (profiled at ~20% of echo_bench CPU
// with a 16-block cache). Tune down on memory-constrained many-core
// hosts (cost scales with thread count).
DEFINE_int32(iobuf_tls_cache_blocks, 512,
             "max free 8KB blocks cached per thread");

namespace tpurpc {

// ---------------- block allocation ----------------

static void* default_blockmem_allocate(size_t n) { return malloc(n); }
static void default_blockmem_deallocate(void* p) { free(p); }

void* (*IOBuf::blockmem_allocate)(size_t) = default_blockmem_allocate;
void (*IOBuf::blockmem_deallocate)(void*) = default_blockmem_deallocate;
bool (*IOBuf::blockmem_cache_veto)(const void*) = nullptr;

namespace {

// Thread-local cache of fully-free default-sized blocks, and the one block
// this thread is currently appending into (shared by all IOBufs of the
// thread — the scheme of reference iobuf.cpp `share_tls_block`, which is
// what makes tail-extension race-free).
struct TLSData {
    IOBuf::Block* append_block = nullptr;
    IOBuf::Block* cache_head = nullptr;
    size_t num_cached = 0;
    ~TLSData();
};


thread_local TLSData tls_data;

// Cross-thread spillover: network pipelines allocate blocks on one thread
// (parser/worker) and free them on another (writer/dispatcher), so TLS
// caches fill where blocks die and run dry where they're born. A small
// global lock-free ring rebalances; capacity bounds idle memory at
// 1024 x 8KB = 8MB process-wide.
MpmcBoundedQueue<IOBuf::Block*>* global_block_ring() {
    static MpmcBoundedQueue<IOBuf::Block*>* r = [] {
        auto* q = new MpmcBoundedQueue<IOBuf::Block*>;
        CHECK_EQ(q->init(1024), 0);
        return q;
    }();
    return r;
}

}  // namespace

IOBuf::Block* IOBuf::create_block(size_t block_size) {
    // Serve default-sized blocks from the TLS cache first — but only blocks
    // created by the CURRENT allocator pair (the pair may be swapped when a
    // transport installs registered memory; stale malloc'd blocks must not
    // be handed out as registered memory).
    if (block_size == DEFAULT_BLOCK_SIZE && tls_data.cache_head != nullptr &&
        tls_data.cache_head->dealloc == blockmem_deallocate) {
        Block* b = tls_data.cache_head;
        tls_data.cache_head = b->portal_next;
        --tls_data.num_cached;
        b->nshared.store(1, std::memory_order_relaxed);
        b->size = 0;
        b->portal_next = nullptr;
        return b;
    }
    if (block_size == DEFAULT_BLOCK_SIZE) {
        Block* b;
        while (global_block_ring()->pop(&b)) {
            if (b->dealloc != blockmem_deallocate) {
                // Stale allocator generation (transport swapped the
                // allocator): free for real and keep draining.
                b->dealloc(b);
                continue;
            }
            b->nshared.store(1, std::memory_order_relaxed);
            b->size = 0;
            b->portal_next = nullptr;
            return b;
        }
    }
    void* mem = blockmem_allocate(block_size);
    if (mem == nullptr) return nullptr;
    Block* b = new (mem) Block;
    b->nshared.store(1, std::memory_order_relaxed);
    b->size = 0;
    b->cap = (uint32_t)(block_size - offsetof(Block, data));
    b->portal_next = nullptr;
    b->dealloc = blockmem_deallocate;
    return b;
}

void IOBuf::Block::dec_ref() {
    if (nshared.fetch_sub(1, std::memory_order_acq_rel) == 1) {
        const size_t total = cap + offsetof(Block, data);
        // Cache only blocks from the current allocator pair.
        const int32_t cache_cap = FLAGS_iobuf_tls_cache_blocks.get();
        if (total == DEFAULT_BLOCK_SIZE && dealloc == blockmem_deallocate &&
            cache_cap > 0 &&
            (blockmem_cache_veto == nullptr || !blockmem_cache_veto(this))) {
            if (tls_data.num_cached < (size_t)cache_cap) {
                portal_next = tls_data.cache_head;
                tls_data.cache_head = this;
                ++tls_data.num_cached;
                return;
            }
            if (global_block_ring()->push(this)) return;
        }
        dealloc(this);
    }
}

TLSData::~TLSData() {
    if (append_block) {
        append_block->dec_ref();
        append_block = nullptr;
    }
    // The cache itself must be freed for real on thread exit, each block
    // through the deallocator it was created with.
    IOBuf::Block* b = cache_head;
    cache_head = nullptr;
    while (b) {
        IOBuf::Block* next = b->portal_next;
        b->dealloc(b);
        b = next;
    }
}

size_t IOBuf::tls_cached_blocks() { return tls_data.num_cached; }

void IOBuf::flush_tls_cache() {
    IOBuf::Block* b = tls_data.cache_head;
    tls_data.cache_head = nullptr;
    tls_data.num_cached = 0;
    while (b) {
        IOBuf::Block* next = b->portal_next;
        b->dealloc(b);
        b = next;
    }
}

// Returns the thread's current append block (holding a TLS ref), creating a
// fresh one when absent or full.
static IOBuf::Block* share_tls_block() {
    IOBuf::Block* b = tls_data.append_block;
    // The allocator-pair check keeps the registered-memory guarantee: once
    // a transport installs its pool, a pre-install malloc'd append block
    // must not keep receiving payload bytes.
    if (b != nullptr && !b->full() &&
        b->dealloc == IOBuf::blockmem_deallocate) {
        return b;
    }
    if (b != nullptr) b->dec_ref();
    b = IOBuf::create_block();
    tls_data.append_block = b;
    return b;
}

// ---------------- view management ----------------

void IOBuf::push_back_ref_(const BlockRef& r) {
    if (is_small()) {
        // Try merging with the previous ref (same block, contiguous).
        if (small_count_ > 0) {
            BlockRef& last = small_[small_count_ - 1];
            if (last.block == r.block && last.offset + last.length == r.offset) {
                last.length += r.length;
                nbytes_ += r.length;
                r.block->dec_ref();  // merged: we don't keep the extra ref
                return;
            }
        }
        if (small_count_ < kInlineRefs) {
            small_[small_count_++] = r;
            nbytes_ += r.length;
            return;
        }
        // Grow into big view.
        BigView bv;
        bv.cap = 8;
        bv.start = 0;
        bv.count = kInlineRefs;
        bv.refs = (BlockRef*)malloc(bv.cap * sizeof(BlockRef));
        memcpy(bv.refs, small_, kInlineRefs * sizeof(BlockRef));
        big_ = bv;
        is_big_ = true;
    }
    // Big view path.
    if (big_.count > 0) {
        BlockRef& last = big_.refs[(big_.start + big_.count - 1) % big_.cap];
        if (last.block == r.block && last.offset + last.length == r.offset) {
            last.length += r.length;
            nbytes_ += r.length;
            r.block->dec_ref();
            return;
        }
    }
    if (big_.count == big_.cap) {
        const uint32_t new_cap = big_.cap * 2;
        BlockRef* new_refs = (BlockRef*)malloc(new_cap * sizeof(BlockRef));
        for (uint32_t i = 0; i < big_.count; ++i) {
            new_refs[i] = big_.refs[(big_.start + i) % big_.cap];
        }
        free(big_.refs);
        big_.refs = new_refs;
        big_.start = 0;
        big_.cap = new_cap;
    }
    big_.refs[(big_.start + big_.count) % big_.cap] = r;
    ++big_.count;
    nbytes_ += r.length;
}

bool IOBuf::cut_front_ref(BlockRef* out) {
    if (nref_() == 0) return false;
    *out = ref_at(0);
    nbytes_ -= out->length;
    // Remove the ref WITHOUT dec_ref: ownership moves to *out.
    if (is_big_) {
        big_.start = (big_.start + 1) % big_.cap;
        --big_.count;
        if (big_.count == 0) {
            free(big_.refs);
            reset_small();
        }
    } else {
        if (small_count_ == 2) small_[0] = small_[1];
        --small_count_;
    }
    return true;
}

void IOBuf::pop_front_ref_() {
    BlockRef& r = ref_at(0);
    nbytes_ -= r.length;
    r.block->dec_ref();
    if (is_big_) {
        big_.start = (big_.start + 1) % big_.cap;
        --big_.count;
        if (big_.count == 0) {
            free(big_.refs);
            reset_small();
        }
    } else {
        if (small_count_ == 2) small_[0] = small_[1];
        --small_count_;
    }
}

void IOBuf::pop_back_ref_() {
    BlockRef& r = ref_at(nref_() - 1);
    nbytes_ -= r.length;
    r.block->dec_ref();
    if (is_big_) {
        --big_.count;
        if (big_.count == 0) {
            free(big_.refs);
            reset_small();
        }
    } else {
        --small_count_;
    }
}

void IOBuf::clear() {
    while (nref_() > 0) pop_back_ref_();
    if (is_big_) {
        free(big_.refs);
        reset_small();
    }
    nbytes_ = 0;
}

void IOBuf::swap(IOBuf& other) {
    char tmp[sizeof(IOBuf)];
    memcpy(tmp, (void*)this, sizeof(IOBuf));
    memcpy((void*)this, (void*)&other, sizeof(IOBuf));
    memcpy((void*)&other, tmp, sizeof(IOBuf));
}

IOBuf::IOBuf(const IOBuf& rhs) {
    reset_small();
    append(rhs);
}

IOBuf::IOBuf(IOBuf&& rhs) noexcept {
    memcpy((void*)this, (void*)&rhs, sizeof(IOBuf));
    rhs.reset_small();
}

IOBuf& IOBuf::operator=(const IOBuf& rhs) {
    if (this != &rhs) {
        clear();
        append(rhs);
    }
    return *this;
}

IOBuf& IOBuf::operator=(IOBuf&& rhs) noexcept {
    if (this != &rhs) {
        clear();
        memcpy((void*)this, (void*)&rhs, sizeof(IOBuf));
        rhs.reset_small();
    }
    return *this;
}

// ---------------- appending ----------------

int IOBuf::append(const void* data, size_t count) {
    const char* p = (const char*)data;
    size_t left = count;
    while (left > 0) {
        Block* b = share_tls_block();
        if (b == nullptr) return -1;
        const size_t copied = std::min((size_t)b->left_space(), left);
        memcpy(b->data + b->size, p, copied);
        BlockRef r{b->size, (uint32_t)copied, b};
        b->size += (uint32_t)copied;
        b->inc_ref();
        push_back_ref_(r);
        p += copied;
        left -= copied;
    }
    return 0;
}

void IOBuf::append(const IOBuf& other) {
    const uint32_t n = other.nref_();
    for (uint32_t i = 0; i < n; ++i) {
        append_ref(other.ref_at(i));
    }
}

void IOBuf::append(IOBuf&& other) {
    if (empty()) {
        swap(other);
        return;
    }
    const uint32_t n = other.nref_();
    for (uint32_t i = 0; i < n; ++i) {
        BlockRef r = other.ref_at(i);
        r.block->inc_ref();
        push_back_ref_(r);
    }
    other.clear();
}

void IOBuf::append_ref(const BlockRef& ref) {
    ref.block->inc_ref();
    push_back_ref_(ref);
}

// ---------------- cutting ----------------

size_t IOBuf::cutn(IOBuf* out, size_t n) {
    size_t moved = 0;
    while (moved < n && nref_() > 0) {
        BlockRef& r = ref_at(0);
        const size_t want = n - moved;
        if (r.length <= want) {
            // Transfer whole ref: no refcount change, ownership moves.
            BlockRef whole = r;
            nbytes_ -= r.length;
            // Manual pop without dec_ref.
            if (is_big_) {
                big_.start = (big_.start + 1) % big_.cap;
                --big_.count;
                if (big_.count == 0) {
                    free(big_.refs);
                    reset_small();
                }
            } else {
                if (small_count_ == 2) small_[0] = small_[1];
                --small_count_;
            }
            moved += whole.length;
            out->push_back_ref_(whole);
        } else {
            BlockRef part{r.offset, (uint32_t)want, r.block};
            r.block->inc_ref();
            r.offset += (uint32_t)want;
            r.length -= (uint32_t)want;
            nbytes_ -= want;
            moved += want;
            out->push_back_ref_(part);
        }
    }
    return moved;
}

size_t IOBuf::cutn(void* out, size_t n) {
    char* p = (char*)out;
    size_t moved = 0;
    while (moved < n && nref_() > 0) {
        BlockRef& r = ref_at(0);
        const size_t want = std::min((size_t)(n - moved), (size_t)r.length);
        memcpy(p + moved, r.block->data + r.offset, want);
        moved += want;
        if (want == r.length) {
            pop_front_ref_();
        } else {
            r.offset += (uint32_t)want;
            r.length -= (uint32_t)want;
            nbytes_ -= want;
        }
    }
    return moved;
}

size_t IOBuf::cutn(std::string* out, size_t n) {
    n = std::min(n, nbytes_);
    const size_t old = out->size();
    out->resize(old + n);
    return cutn(&(*out)[old], n);
}

int IOBuf::cut1(char* c) {
    if (empty()) return -1;
    return cutn(c, 1) == 1 ? 0 : -1;
}

size_t IOBuf::pop_front(size_t n) {
    size_t popped = 0;
    while (popped < n && nref_() > 0) {
        BlockRef& r = ref_at(0);
        const size_t want = std::min((size_t)(n - popped), (size_t)r.length);
        if (want == r.length) {
            pop_front_ref_();
        } else {
            r.offset += (uint32_t)want;
            r.length -= (uint32_t)want;
            nbytes_ -= want;
        }
        popped += want;
    }
    return popped;
}

size_t IOBuf::pop_back(size_t n) {
    size_t popped = 0;
    while (popped < n && nref_() > 0) {
        BlockRef& r = ref_at(nref_() - 1);
        const size_t want = std::min((size_t)(n - popped), (size_t)r.length);
        if (want == r.length) {
            pop_back_ref_();
        } else {
            r.length -= (uint32_t)want;
            nbytes_ -= want;
        }
        popped += want;
    }
    return popped;
}

// ---------------- reading ----------------

size_t IOBuf::copy_to(void* buf, size_t n, size_t pos) const {
    char* p = (char*)buf;
    size_t copied = 0;
    const uint32_t cnt = nref_();
    for (uint32_t i = 0; i < cnt && copied < n; ++i) {
        const BlockRef& r = ref_at(i);
        if (pos >= r.length) {
            pos -= r.length;
            continue;
        }
        const size_t avail = r.length - pos;
        const size_t want = std::min(n - copied, avail);
        memcpy(p + copied, r.block->data + r.offset + pos, want);
        copied += want;
        pos = 0;
    }
    return copied;
}

uint32_t IOBuf::copy_to_crc32c(void* dst, size_t cap, size_t pos,
                               size_t* copied) const {
    char* d = (char*)dst;
    size_t done = 0;
    uint32_t crc = 0;
    const uint32_t cnt = nref_();
    for (uint32_t i = 0; i < cnt && done < cap; ++i) {
        const BlockRef& r = ref_at(i);
        if (pos >= r.length) {
            pos -= r.length;
            continue;
        }
        const size_t want = std::min(cap - done, (size_t)r.length - pos);
        crc = crc32c_copy_extend(crc, d + done,
                                 r.block->data + r.offset + pos, want);
        done += want;
        pos = 0;
    }
    if (copied != nullptr) *copied = done;
    static const char kZeros[4096] = {};
    for (; done < cap; done += std::min(sizeof(kZeros), cap - done)) {
        crc = crc32c_copy_extend(crc, d + done, kZeros,
                                 std::min(sizeof(kZeros), cap - done));
    }
    return crc;
}

size_t IOBuf::copy_to(std::string* s, size_t n, size_t pos) const {
    if (pos >= nbytes_) {
        s->clear();
        return 0;
    }
    n = std::min(n, nbytes_ - pos);
    s->resize(n);
    return copy_to(&(*s)[0], n, pos);
}

std::string IOBuf::to_string() const {
    std::string s;
    copy_to(&s);
    return s;
}

const void* IOBuf::fetch(void* aux, size_t n) const {
    if (n > nbytes_) return nullptr;
    if (n == 0) return aux;
    const BlockRef& r = ref_at(0);
    if (r.length >= n) {
        return r.block->data + r.offset;
    }
    copy_to(aux, n);
    return aux;
}

int IOBuf::front_byte() const {
    if (empty()) return -1;
    const BlockRef& r = ref_at(0);
    return (unsigned char)r.block->data[r.offset];
}

bool IOBuf::equals(const std::string& s) const {
    if (s.size() != nbytes_) return false;
    size_t off = 0;
    const uint32_t cnt = nref_();
    for (uint32_t i = 0; i < cnt; ++i) {
        const BlockRef& r = ref_at(i);
        if (memcmp(s.data() + off, r.block->data + r.offset, r.length) != 0) {
            return false;
        }
        off += r.length;
    }
    return true;
}

const char* IOBuf::backing_block_data(size_t i, size_t* len) const {
    if (i >= nref_()) {
        *len = 0;
        return nullptr;
    }
    const BlockRef& r = ref_at((uint32_t)i);
    *len = r.length;
    return r.block->data + r.offset;
}

// ---------------- fd I/O ----------------

static constexpr size_t kMaxIov = 64;

ssize_t IOBuf::cut_into_file_descriptor(int fd, size_t size_hint) {
    iovec vec[kMaxIov];
    size_t nvec = 0;
    size_t total = 0;
    const uint32_t cnt = nref_();
    for (uint32_t i = 0; i < cnt && nvec < kMaxIov && total < size_hint; ++i) {
        const BlockRef& r = ref_at(i);
        vec[nvec].iov_base = r.block->data + r.offset;
        vec[nvec].iov_len = r.length;
        total += r.length;
        ++nvec;
    }
    if (nvec == 0) return 0;
    ssize_t written = writev(fd, vec, (int)nvec);
    if (written > 0) pop_front((size_t)written);
    return written;
}

ssize_t IOBuf::cut_multiple_into_file_descriptor(int fd, IOBuf* const* pieces,
                                                 size_t count) {
    iovec vec[kMaxIov];
    size_t nvec = 0;
    for (size_t p = 0; p < count && nvec < kMaxIov; ++p) {
        const IOBuf* buf = pieces[p];
        const uint32_t cnt = buf->nref_();
        for (uint32_t i = 0; i < cnt && nvec < kMaxIov; ++i) {
            const BlockRef& r = buf->ref_at(i);
            vec[nvec].iov_base = r.block->data + r.offset;
            vec[nvec].iov_len = r.length;
            ++nvec;
        }
    }
    if (nvec == 0) return 0;
    ssize_t written = writev(fd, vec, (int)nvec);
    if (written > 0) {
        size_t left = (size_t)written;
        for (size_t p = 0; p < count && left > 0; ++p) {
            left -= pieces[p]->pop_front(left);
        }
    }
    return written;
}

// ---------------- IOPortal ----------------

IOPortal::~IOPortal() {
    if (block_) {
        block_->dec_ref();
        block_ = nullptr;
    }
}

void IOPortal::return_cached_blocks() {
    if (block_) {
        block_->dec_ref();
        block_ = nullptr;
    }
}

ssize_t IOPortal::append_from_file_descriptor(int fd, size_t max_count) {
    // Assemble an iovec over [tail of current block] + fresh blocks.
    constexpr size_t kReadVecs = 64;
    iovec vec[kReadVecs];
    Block* blocks[kReadVecs];
    size_t nvec = 0;
    size_t space = 0;
    if (block_ != nullptr && !block_->full()) {
        blocks[nvec] = block_;
        vec[nvec].iov_base = block_->data + block_->size;
        vec[nvec].iov_len = block_->left_space();
        space += block_->left_space();
        ++nvec;
    }
    while (space < max_count && nvec < kReadVecs) {
        Block* b = create_block();
        if (b == nullptr) break;
        blocks[nvec] = b;
        vec[nvec].iov_base = b->data;
        vec[nvec].iov_len = b->cap;
        space += b->cap;
        ++nvec;
    }
    if (nvec == 0) {
        errno = ENOMEM;
        return -1;
    }
    ssize_t nr = readv(fd, vec, (int)nvec);
    if (nr <= 0) {
        // Release blocks we created (index 0 may be the retained block_).
        for (size_t i = 0; i < nvec; ++i) {
            if (blocks[i] != block_) blocks[i]->dec_ref();
        }
        return nr;
    }
    size_t left = (size_t)nr;
    Block* new_current = nullptr;
    for (size_t i = 0; i < nvec; ++i) {
        Block* b = blocks[i];
        const size_t cap_here = vec[i].iov_len;
        const size_t fill = std::min(left, cap_here);
        if (fill > 0) {
            BlockRef r{b->size, (uint32_t)fill, b};
            b->size += (uint32_t)fill;
            b->inc_ref();
            push_back_ref_(r);
            left -= fill;
        }
        if (fill < cap_here && left == 0 && new_current == nullptr && !b->full()) {
            // Keep the first partially-empty block for the next read.
            new_current = b;
            continue;  // retains the ref we hold on it
        }
        if (b != new_current) {
            // Fully used (ref now held by the buf) or untouched: drop our ref
            // unless it's the old block_ that became the new current.
            if (b == block_) {
                // old current: either full (drop) or it became new_current above
                if (b != new_current) {
                    b->dec_ref();
                }
            } else {
                b->dec_ref();
            }
        }
    }
    block_ = new_current;
    return nr;
}

}  // namespace tpurpc
