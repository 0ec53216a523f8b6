"""Built-in scenarios for the unified Job API, each with a numpy oracle.

Three use-cases demonstrate the protocol's range on the same engines:

  * :class:`WordCount`     — the paper's §3.1 PUMA benchmark: <token, 1>.
  * :class:`Histogram`     — bin token ids into B buckets: <bin, 1> (a
                             different key space than the emit domain).
  * :class:`InvertedIndex` — grep-style posting lists with term
                             frequencies: for a query set Q and documents
                             made of consecutive tasks, emit
                             <doc·|Q|+q, 1> — a positional scenario only
                             possible now that ``map_emit`` sees the
                             global task id.
  * :class:`PostingsIndex` — PUMA Inverted-Index: <(word, document), 1>
                             with two-lane keys, every word's postings
                             with term frequencies; the same postings as
                             :class:`InvertedIndex` before restriction
                             to a query set.

All values are additive (the engines' Reduce is an exact keyed sum), so
every scenario is oracle-exact on both the ``"1s"`` and ``"2s"``
backends, balanced or not.
"""
from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core.kv import KEY_SENTINEL
from repro.core.records import as_records


# ---------------------------------------------------------------------------
# WordCount
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class WordCount:
    """<token, 1>: counts occurrences of each token id."""
    vocab: int

    @property
    def window(self) -> int:
        return self.vocab

    def map_emit(self, tokens, task_id):
        valid = tokens != KEY_SENTINEL
        return tokens, jnp.where(valid, 1, 0).astype(jnp.int32)


def wordcount_oracle(tokens, vocab: int) -> dict[int, int]:
    """numpy reference: exact counts over the whole input."""
    tokens = np.asarray(tokens)
    tokens = tokens[tokens != int(KEY_SENTINEL)]
    counts = np.bincount(tokens, minlength=vocab)
    keys = np.nonzero(counts)[0]
    return {int(k): int(counts[k]) for k in keys}


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Histogram:
    """<bin, 1>: equal-width histogram of token ids over [0, vocab)."""
    vocab: int
    n_bins: int

    @property
    def window(self) -> int:
        return self.n_bins

    def __post_init__(self):
        # bin mapping is computed in int32 (x64 may be disabled)
        assert self.vocab * self.n_bins < 2 ** 31, "vocab*n_bins overflows"

    def map_emit(self, tokens, task_id):
        valid = tokens != KEY_SENTINEL
        bins = jnp.where(valid, tokens, 0) * self.n_bins // self.vocab
        keys = jnp.where(valid, bins, KEY_SENTINEL)
        return keys, jnp.where(valid, 1, 0).astype(jnp.int32)

    def finalize(self, records: Mapping[int, int]) -> np.ndarray:
        r = as_records(records)
        out = np.zeros((self.n_bins,), np.int64)
        out[r.key_array] = r.value_array
        return out


def histogram_oracle(tokens, vocab: int, n_bins: int) -> np.ndarray:
    tokens = np.asarray(tokens)
    tokens = tokens[tokens != int(KEY_SENTINEL)]
    bins = tokens.astype(np.int64) * n_bins // vocab
    return np.bincount(bins, minlength=n_bins).astype(np.int64)


# ---------------------------------------------------------------------------
# InvertedIndex (grep with term frequencies)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InvertedIndex:
    """Posting lists for a query set: key = doc · |Q| + query_index.

    A "document" is ``tasks_per_doc`` consecutive Map tasks — derived
    from the global ``task_id``, which is why this scenario needs the
    redesigned ``map_emit(tokens, task_id)`` signature.
    """
    queries: tuple          # token ids to index (hashable for dataclass)
    n_docs: int
    tasks_per_doc: int

    @property
    def window(self) -> int:
        return self.n_docs * len(self.queries)

    def map_emit(self, tokens, task_id):
        q = jnp.asarray(self.queries, jnp.int32)            # (Q,)
        eq = tokens[:, None] == q[None, :]                  # (S, Q)
        qidx = jnp.argmax(eq, axis=1).astype(jnp.int32)
        hit = eq.any(axis=1) & (tokens != KEY_SENTINEL) & (task_id >= 0)
        doc = jnp.clip(task_id // self.tasks_per_doc, 0, self.n_docs - 1)
        keys = jnp.where(hit, doc * len(self.queries) + qidx, KEY_SENTINEL)
        return keys.astype(jnp.int32), jnp.where(hit, 1, 0).astype(jnp.int32)

    def finalize(self, records: Mapping[int, int]
                 ) -> dict[int, dict[int, int]]:
        """{query_token: {doc: term_frequency}} — sparse posting lists."""
        r = as_records(records)
        out: dict[int, dict[int, int]] = {int(t): {} for t in self.queries}
        doc, qidx = np.divmod(r.key_array, len(self.queries))
        for i, t in enumerate(self.queries):
            hit = qidx == i
            out[int(t)].update(zip(doc[hit].tolist(),
                                   r.value_array[hit].tolist()))
        return out


def inverted_index_oracle(tokens, queries, task_size: int,
                          tasks_per_doc: int, n_docs: int):
    """numpy reference mirroring the planner's task slicing."""
    tokens = np.asarray(tokens)
    out = {int(t): {} for t in queries}
    n_tasks = (len(tokens) + task_size - 1) // task_size
    for t in range(n_tasks):
        doc = min(t // tasks_per_doc, n_docs - 1)
        chunk = tokens[t * task_size: (t + 1) * task_size]
        chunk = chunk[chunk != int(KEY_SENTINEL)]
        for q in queries:
            n = int((chunk == q).sum())
            if n:
                d = out[int(q)]
                d[doc] = d.get(doc, 0) + n
    return out


# ---------------------------------------------------------------------------
# PostingsIndex (PUMA Inverted-Index)
# ---------------------------------------------------------------------------

class Postings(NamedTuple):
    """Posting lists in CSR form: the documents holding ``words[i]`` are
    ``docs[offsets[i]:offsets[i + 1]]``, ascending, with their term
    frequencies in ``freqs`` alongside."""
    words: np.ndarray       # (n_words,) int64, ascending
    offsets: np.ndarray     # (n_words + 1,) int64
    docs: np.ndarray        # (n_postings,) int64
    freqs: np.ndarray       # (n_postings,) int64


@dataclass(frozen=True)
class PostingsIndex:
    """<(word, document), 1>: PUMA's Inverted-Index with term frequencies.

    A document is ``doc_len`` consecutive tokens of the job's input: the
    token at index i of task t lies in document ``(t · S + i) // doc_len``,
    S being the task size, so documents straddle tasks and segments
    freely. The key has two lanes, word (major, below ``vocab``) and
    document (minor), so it needs no dense window: the engine keeps the
    records in a log window. Positions are int32, so a job holds fewer
    than 2^31 tokens.
    """
    vocab: int
    doc_len: int

    key_lanes = 2

    @property
    def window(self) -> int:
        return self.vocab

    def map_emit(self, tokens, task_id):
        S = tokens.shape[0]
        valid = (tokens != KEY_SENTINEL) & (task_id >= 0)
        doc = (task_id * S + jnp.arange(S, dtype=jnp.int32)) // self.doc_len
        keys = (jnp.where(valid, tokens, KEY_SENTINEL),
                jnp.where(valid, doc, KEY_SENTINEL))
        return keys, jnp.where(valid, 1, 0).astype(jnp.int32)

    def finalize(self, records: Mapping[int, int]) -> Postings:
        """The records ``{(word << 32) | doc: term frequency}`` as CSR
        posting lists, straight from the sorted key and value arrays."""
        r = as_records(records)
        keys = r.key_array.astype(np.int64, copy=False)
        freqs = r.value_array.astype(np.int64)
        words = keys >> 32
        starts = np.flatnonzero(np.diff(words, prepend=-1))
        return Postings(words[starts], np.append(starts, keys.size),
                        keys & 0xFFFFFFFF, freqs)
