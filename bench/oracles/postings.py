"""Plain reference for PUMA Inverted-Index with term frequencies: each
(word, document) pair's count.

numpy only; it imports nothing of the system under test. A document is
``doc_len`` consecutive tokens of the job's input, so the token at
position p lies in document ``p // doc_len``. A record's key is
``(word << 32) | document``, as the program returns it.

``control`` is the same reference with the pair packed into one 32-bit
key, ``word * 2^ceil(log2 n_docs) + document`` wrapping mod 2^32: what a
one-lane int32 key would compute. Word ids at or above
``2^(32 - ceil(log2 n_docs))`` then wrap onto lower ones.
"""
from __future__ import annotations

import numpy as np


def _documents(tokens: np.ndarray, doc_len: int) -> np.ndarray:
    return np.arange(np.asarray(tokens).size, dtype=np.int64) // doc_len


def _records(keys: np.ndarray) -> dict[int, int]:
    keys, counts = np.unique(keys, return_counts=True)
    return dict(zip(keys.tolist(), counts.tolist()))


def reference(tokens: np.ndarray, *, vocab: int,
              doc_len: int) -> dict[int, int]:
    """{(word << 32) | document: term frequency} for every pair that
    occurs."""
    words = np.asarray(tokens).astype(np.int64)
    return _records((words << 32) | _documents(tokens, doc_len))


def control(tokens: np.ndarray, *, vocab: int,
            doc_len: int) -> dict[int, int]:
    """:func:`reference` through a one-lane 32-bit key: the pair packed
    into the low 32 bits, counted, and unpacked again."""
    words = np.asarray(tokens).astype(np.int64)
    docs = _documents(tokens, doc_len)
    bits = max(int(docs[-1]) if docs.size else 0, 1).bit_length()
    packed = ((words << bits) + docs) & 0xFFFFFFFF
    keys, counts = np.unique(packed, return_counts=True)
    keys = ((keys >> bits) << 32) | (keys & ((1 << bits) - 1))
    return dict(zip(keys.tolist(), counts.tolist()))
