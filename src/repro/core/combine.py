"""Combine phase — tree-based merge of per-process sorted results.

Paper §2.1 / Fig 3: ⌈log2(P)⌉ + 1 levels; level 0 is each process's local
in-order records; at every further level, rank i+2^l sends its current run to
rank i (one-sided get in the paper → ``collective_permute`` here) which merges
the two sorted runs, summing duplicate keys (this also resolves the records
whose ownership was transferred during Map overflow). After the last level,
rank 0 holds the globally sorted result.

MPI_LOCK_EXCLUSIVE has no analogue (and no need): SPMD lockstep already
serializes levels.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.kv import local_reduce, sort_reduce


def n_levels(n_procs: int) -> int:
    return int(math.ceil(math.log2(max(n_procs, 2))))


# Overflow totals accumulate across ranks (psum) and tree levels in int32
# (jnp.int64 silently degrades to int32 without x64, so widening is not an
# option here). A wrapped counter could report 0 lost records after losing
# 2^32 — saturating at INT32_MAX keeps the "0 means exact" contract.
SAT_MAX = jnp.iinfo(jnp.int32).max


def sat_add_i32(a, b):
    """Saturating int32 add for non-negative operands: wrap -> SAT_MAX."""
    s = a + b
    return jnp.where(s < a, jnp.int32(SAT_MAX), s)


def _sat_psum(x, axis: str, n_procs: int):
    """psum of non-negative int32 counts that cannot wrap: each rank's
    contribution is pre-clamped to SAT_MAX // P so the P-way sum stays
    inside int32; a clamped contribution already means the true total
    saturates."""
    cap = jnp.int32(SAT_MAX // max(n_procs, 1))
    return lax.psum(jnp.minimum(x.astype(jnp.int32), cap), axis)


def tree_combine(keys, vals, axis: str, n_procs: int, overflow=None):
    """Run the merge tree inside a shard_map region.

    keys/vals: this process's sorted unique records, (W,), sentinel-padded;
    for two-lane keys ``keys`` is a (major, minor) pair of such arrays,
    merged by :func:`~repro.core.kv.sort_reduce`.
    ``overflow`` seeds the per-rank count of records already lost before
    the tree (e.g. squeezing a window into W — see ``combine_records``).

    Returns ``(keys, vals, total_overflow)``: rank 0 holds the final
    merged records (other ranks return their last partial state —
    callers slice rank 0), while ``total_overflow`` is the *global*
    count of records dropped anywhere on the way to rank 0 — each
    W-wide merge of two runs whose key union exceeds W truncates the
    union, and that loss used to vanish silently at the next level.
    The count is psum-replicated, so every rank returns the same value
    and a 0 guarantees the rank-0 records are exact. It saturates at
    ``SAT_MAX`` instead of wrapping, so a huge loss can never read as 0.
    """
    merge = sort_reduce if isinstance(keys, tuple) else local_reduce
    with jax.named_scope("tree"):
        W = vals.shape[0]
        rank = lax.axis_index(axis)
        if overflow is None:
            overflow = jnp.int32(0)
        total = _sat_psum(overflow, axis, n_procs)
        for level in range(n_levels(n_procs)):
            stride = 1 << level
            perm = [(i + stride, i) for i in range(0, n_procs, stride * 2)
                    if i + stride < n_procs]
            rk = jax.tree.map(lambda k: lax.ppermute(k, axis, perm), keys)
            rv = lax.ppermute(vals, axis, perm)
            # ppermute delivers zeros to non-receivers; treat key 0 as
            # valid only on true receivers by masking the merge with
            # receiver-ship.
            is_receiver = ((rank % (stride * 2) == 0)
                           & (rank + stride < n_procs))
            mk, mv, n_union = merge(
                jax.tree.map(lambda a, b: jnp.concatenate([a, b]), keys,
                             rk),
                jnp.concatenate([vals, rv]), W)
            lost = jnp.where(
                is_receiver, jnp.maximum(n_union.astype(jnp.int32) - W, 0),
                0)
            total = sat_add_i32(total, _sat_psum(lost, axis, n_procs))
            keys = jax.tree.map(lambda m, k: jnp.where(is_receiver, m, k),
                                mk, keys)
            vals = jnp.where(is_receiver, mv, vals)
        return keys, vals, total
