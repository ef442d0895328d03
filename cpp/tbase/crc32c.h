// CRC32-C (Castagnoli, polynomial 0x1EDC6F41 reflected 0x82F63B78):
// the frame checksum of the RPC layer.
//
// Reference: src/butil/crc32c.{h,cc} (hardware SSE4.2 path + table
// fallback). One routine (crc32c.cc) serves the checksum and the
// checksummed copy: the SSE4.2 CRC32 instruction over three interleaved
// streams where the cpu has it, slice-by-8 tables elsewhere.
#pragma once

#include <cstddef>
#include <cstdint>

namespace tpurpc {

// Extend a running crc with [data, data+n). Start with crc = 0.
uint32_t crc32c_extend(uint32_t crc, const void* data, size_t n);

inline uint32_t crc32c(const void* data, size_t n) {
    return crc32c_extend(0, data, n);
}

// memcpy(dst, src, n) that returns crc32c_extend(crc, src, n), in one pass:
// every line of src is folded into the crc and copied while it sits in
// L1, so the bytes come from memory once and go to memory once, and the
// crc is the SOURCE's: a bad copy does not checksum clean. Any alignment,
// any length; [dst, dst+n) and [src, src+n) must not overlap.
uint32_t crc32c_copy_extend(uint32_t crc, void* dst, const void* src,
                            size_t n);

// The same on the table path whatever the cpu (tests hold the two paths
// to each other). dst == nullptr: checksum only.
uint32_t crc32c_copy_extend_tables(uint32_t crc, void* dst, const void* src,
                                   size_t n);

}  // namespace tpurpc
