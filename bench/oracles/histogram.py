"""Plain reference for Histogram (PUMA Histogram-Ratings): counts per bin.

A token id t in [0, vocab) falls into bin ``t * n_bins // vocab``. numpy
only; it imports nothing of the system under test. ``control`` is the
same reference with int16 accumulators, one precision below the
configuration's exact int32 counts; they wrap past 32,767.
"""
from __future__ import annotations

import numpy as np


def _bins(tokens, vocab: int, n_bins: int) -> np.ndarray:
    t = np.asarray(tokens).astype(np.int64)
    return np.bincount(t * n_bins // vocab, minlength=n_bins)


def _records(counts: np.ndarray) -> dict[int, int]:
    keys = np.flatnonzero(counts)
    return dict(zip(keys.tolist(), counts[keys].tolist()))


def reference(tokens: np.ndarray, *, vocab: int,
              n_bins: int) -> dict[int, int]:
    """{bin: count} for each bin that holds a token."""
    return _records(_bins(tokens, vocab, n_bins))


def control(tokens: np.ndarray, *, vocab: int,
            n_bins: int) -> dict[int, int]:
    """:func:`reference` with int16 counts."""
    return _records(_bins(tokens, vocab, n_bins).astype(np.int16))
