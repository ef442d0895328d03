"""Plain references: the same semantics written the obvious way.

Nothing here imports the program (`brpc_tpu`, `libtpurpc.so`) or takes
anything it made. Inputs come from `payload.py`; outputs are what the
timed path must have produced.
"""
import numpy as np


def integrity_word(words: np.ndarray) -> int:
    """The order-sensitive word the device pass must return for a chunk:
    sum over i of words[i] * (2i + 1), all in uint32 wraparound. Written
    with Python integers over uint64 partial products, not as the program
    writes it."""
    w = np.asarray(words, dtype=np.uint64)
    mult = (np.arange(w.size, dtype=np.uint64) * np.uint64(2)
            + np.uint64(1))
    prod = (w * mult) & np.uint64(0xFFFFFFFF)  # < 2**32 * 2**32: no wrap
    return int(prod.sum(dtype=np.uint64)) & 0xFFFFFFFF


def check_ring(chunks, returned: dict, dev_words, launched: int) -> dict:
    """Hold the ring's answers to the reference.

    chunks: the uint32 chunks as made from the seed. returned: {k: bytes
    that came back for chunk k} (the sample: each chunk's last answer).
    dev_words: the device's integrity word of EVERY chunk retired, in
    launch order (chunk k of pass p at index p*len(chunks)+k). launched:
    how many chunks were started; each must have been answered.
    Returns the numbers compared."""
    want_words = [integrity_word(c) for c in chunks]
    n = len(chunks)
    words_wrong = sum(1 for i, got in enumerate(dev_words)
                      if int(got) != want_words[i % n])
    bytes_wrong = sum(
        1 for k, got in returned.items()
        if not np.array_equal(np.asarray(got).view(np.uint8).reshape(-1),
                              chunks[k].view(np.uint8)))
    return {"chunks_bytes_wrong": bytes_wrong,
            "chunks_word_wrong": words_wrong,
            "chunks_unanswered": (min(n, launched) - len(returned)
                                  + launched - len(dev_words))}
