"""Plain reference for WordCount (PUMA WordCount): each word id's count.

numpy only; it imports nothing of the system under test. ``control`` is
the same reference computed one precision below the configuration's exact
int32 counts: int16 accumulators, which wrap past 32,767. A later change
that narrowed the key window's counts to halve its memory would read so.
"""
from __future__ import annotations

import numpy as np


def _records(counts: np.ndarray) -> dict[int, int]:
    keys = np.flatnonzero(counts)
    return dict(zip(keys.tolist(), counts[keys].tolist()))


def reference(tokens: np.ndarray, *, vocab: int) -> dict[int, int]:
    """{word id: count} over every token, for each word that occurs."""
    return _records(np.bincount(np.asarray(tokens), minlength=vocab))


def control(tokens: np.ndarray, *, vocab: int) -> dict[int, int]:
    """:func:`reference` with int16 counts (a cast wraps as int16
    accumulation would)."""
    counts = np.bincount(np.asarray(tokens), minlength=vocab)
    return _records(counts.astype(np.int16))
