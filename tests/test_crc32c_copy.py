"""`crc32c_copy_extend` (ISSUE 30) through the C API: the copy that is the
checksum pass. Held, on the hardware path and on the table path, to
`crc32c_extend` and to a plain table crc written here, over every pair of
source and destination alignments; `tests/test_cpp.py` is red on the seed,
so the guard lives here."""
import numpy as np
import pytest

LENGTHS = [0, 1, 7, 8, 9, 767, 768, 4095, 4096, 24576 + 9, 1 << 20,
           (1 << 20) + 3]
INIT = 0x30C0FFEE


@pytest.fixture(scope="module")
def native(cpp_build):
    from brpc_tpu import native as n

    n.lib()
    return n


def plain_crc32c(data: bytes, crc: int) -> int:
    table = []
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ 0x82F63B78 if c & 1 else c >> 1
        table.append(c)
    crc ^= 0xFFFFFFFF
    for b in data:
        crc = table[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


@pytest.mark.parametrize("tables", [False, True],
                         ids=["cpu_path", "table_path"])
@pytest.mark.parametrize("n", LENGTHS)
def test_the_copy_is_the_checksum_pass(native, n, tables):
    rng = np.random.default_rng(n)
    src_room = rng.integers(0, 256, n + 16, dtype=np.uint8)
    for src_at in range(8):
        src = src_room[src_at:src_at + n]
        want = native.crc32c(src, INIT)
        if n <= 4096:
            assert want == plain_crc32c(src.tobytes(), INIT)
        else:  # in two parts, cut off every lane's boundary
            assert want == native.crc32c(src[1001:],
                                         native.crc32c(src[:1001], INIT))
        for dst_at in range(8):
            dst_room = np.full(n + 24, 0xAA, dtype=np.uint8)
            dst = dst_room[8 + dst_at:8 + dst_at + n]
            got = native.copy_crc32c(dst, src, INIT, tables=tables)
            assert got == want, (src_at, dst_at)
            assert dst.tobytes() == src.tobytes(), (src_at, dst_at)
            # Not a byte before or behind.
            assert (dst_room[:8 + dst_at] == 0xAA).all()
            assert (dst_room[8 + dst_at + n:] == 0xAA).all()


def test_the_table_path_alone_checks_without_a_copy(native):
    import ctypes

    src = np.arange(5000, dtype=np.uint8)
    got = native.lib().tpurpc_crc32c_copy_tables(
        INIT, None, ctypes.c_void_p(src.ctypes.data), src.nbytes)
    assert got == native.crc32c(src, INIT) == plain_crc32c(src.tobytes(),
                                                           INIT)


def test_rfc3720_vectors_through_the_copy(native):
    for data, want in ((b"123456789", 0xE3069283), (bytes(32), 0x8A9136AA),
                       (b"\xff" * 32, 0x62A8AB43),
                       (bytes(range(32)), 0x46DD794E)):
        src = np.frombuffer(data, dtype=np.uint8)
        for tables in (False, True):
            dst = np.empty_like(src)
            assert native.copy_crc32c(dst, src, tables=tables) == want
            assert dst.tobytes() == data
        assert native.crc32c(data) == want


def test_sizes_must_agree(native):
    with pytest.raises(ValueError):
        native.copy_crc32c(np.empty(8, np.uint8), np.empty(9, np.uint8))
