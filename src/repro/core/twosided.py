"""MapReduce-2S — the bulk-synchronous reference (Hoefler et al. [7]).

Same Map / Local Reduce / mapping / bucket memory management as MR-1S (the
paper keeps these identical on purpose), but:

  * all Map tasks complete first, buffering *every* task's buckets
    (this is why its memory footprint scales with total map output — Fig 6);
  * one bulk all_to_all (MPI_Alltoallv analogue) shuffles everything after
    the implicit barrier;
  * Reduce runs as one post-shuffle spike;
  * the Combine tree is shared with MR-1S (point-to-point in the paper; the
    ppermute tree is the faithful analogue of both variants on TPU).

Master-slave MPI_Scatter task distribution maps to the initial sharded
device_put of the task grid (the host "master" owns placement).

Registered as backend ``"2s"`` (:mod:`repro.core.registry`). Through the
shared Backend protocol it also exposes a segmented path: between two
window syncs the engine is classically bulk-synchronous *over that
segment* (map-all, barrier, bulk shuffle, reduce spike), and the dense
Key-Value window carried across segments is what the checkpoint layer
snapshots — the same :class:`~repro.core.windows.EngineCarry` type as
MR-1S, with the in-flight ``pending_*`` buffers simply left empty.
"""
from __future__ import annotations

from functools import partial
from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from repro.core.combine import tree_combine
from repro.core.kv import reduce_and_bucketize
from repro.core.partition import lookup_owner
from repro.core.registry import JobSpec, memoized, register_backend
from repro.core.windows import (AXIS, DenseWindow, combine_records,
                                init_carry, wrap_segment_fns)
from repro.distributed.collectives import all_to_all_blocks


def _map_all(spec: JobSpec, map_fn: Callable, tokens, task_ids, repeats,
             owner_map, owner_split):
    """The bulk Map phase over a task grid: every task's buckets are
    buffered before anything is sent (the 2S memory spike)."""
    P, cap = spec.n_procs, spec.push_cap
    T = tokens.shape[0]

    def map_one(_, xs):
        task, tid, rep = xs
        keys, vals = map_fn(task, tid, rep)
        # same repeated task compute as MR-1S (the engines share the Map /
        # Local Reduce mechanics by design — paper §2.2.1)
        owners = lookup_owner(owner_map, owner_split, keys, tid, P)
        bk, bv, counts, (ofk, ofv) = reduce_and_bucketize(
            keys, vals, owners, P, cap, rep)
        return None, (bk, bv, ofk, ofv)

    _, (BK, BV, OFK, OFV) = lax.scan(map_one, None,
                                     (tokens, task_ids, repeats))
    # (T, P, cap) -> (P, T*cap): the full send buffer
    BK = jnp.swapaxes(BK, 0, 1).reshape(P, T * cap)
    BV = jnp.swapaxes(BV, 0, 1).reshape(P, T * cap)
    return BK, BV, OFK, OFV


def _shuffle_reduce(win: DenseWindow, BK, BV, OFK, OFV) -> DenseWindow:
    """Barrier + bulk shuffle (MPI_Alltoallv), then the Reduce spike."""
    RK = all_to_all_blocks(BK, AXIS)
    RV = all_to_all_blocks(BV, AXIS)
    win = win.put(RK.reshape(-1), RV.reshape(-1))
    return win.put(OFK.reshape(-1), OFV.reshape(-1))  # overflow kept local


def _engine(spec: JobSpec, map_fn: Callable, tokens, task_ids, repeats):
    from repro.core.kv import owner_of
    tokens, task_ids, repeats = tokens[0], task_ids[0], repeats[0]
    # legacy blocking path: always the hash rule (the Job API's segmented
    # path carries skew-aware maps in the EngineCarry)
    omap = owner_of(jnp.arange(spec.vocab, dtype=jnp.int32), spec.n_procs)
    osplit = jnp.ones((spec.vocab,), jnp.int32)
    BK, BV, OFK, OFV = _map_all(spec, map_fn, tokens, task_ids, repeats,
                                omap, osplit)
    win = DenseWindow(jnp.zeros((spec.vocab,), jnp.int32))
    win = _shuffle_reduce(win, BK, BV, OFK, OFV)
    # ---- Combine ----------------------------------------------------------
    keys, vals, overflow = combine_records(win.table, spec)
    keys, vals, _ = tree_combine(keys, vals, AXIS, spec.n_procs, overflow)
    return keys[None], vals[None]


@register_backend("2s")
class TwoSidedBackend:
    """The bulk-synchronous engine behind the ``Backend`` protocol."""

    def __init__(self):
        self._programs: dict = {}

    def run_job(self, spec: JobSpec, map_fn: Callable, mesh, tokens,
                task_ids, repeats):
        from jax.sharding import PartitionSpec as P
        fn = memoized(
            self._programs, ("run", spec, map_fn, mesh),
            lambda: jax.jit(shard_map(
                partial(_engine, spec, map_fn), mesh=mesh,
                in_specs=(P(AXIS), P(AXIS), P(AXIS)),
                out_specs=(P(AXIS), P(AXIS)))))
        keys, vals = fn(tokens, task_ids, repeats)
        return jax.device_get(keys)[0], jax.device_get(vals)[0]

    def trace_handles(self, spec: JobSpec, map_fn: Callable, mesh,
                      seg_tasks: int = 2, tag: str = ""):
        """Traceable :class:`~repro.core.registry.ProgramHandle`\\ s for
        fleetlint (repro.analysis)."""
        from repro.core.registry import segment_program_handles
        return segment_program_handles(self, spec, map_fn, mesh,
                                       seg_tasks=seg_tasks, tag=tag)

    def make_segment_fns(self, spec: JobSpec, map_fn: Callable, mesh):
        """Segmented 2S: each segment runs bulk-synchronously (map-all,
        bulk shuffle, reduce spike) and folds into the carried window —
        the window sync point the checkpoint layer snapshots."""
        return memoized(self._programs, ("seg", spec, map_fn, mesh),
                        lambda: self._build_segment_fns(spec, map_fn, mesh))

    def _build_segment_fns(self, spec: JobSpec, map_fn: Callable, mesh):
        if spec.coslots > 1:
            # no supports_coschedule: the bulk path never learned to
            # route composite keys — reject instead of mis-reducing
            raise ValueError(
                "backend '2s' does not support cross-job co-scheduling "
                "(coslots > 1) — WorkDomains form over '1s' only")

        def seg(carry, tok, tid, rep):
            BK, BV, OFK, OFV = _map_all(spec, map_fn, tok, tid, rep,
                                        carry.owner_map, carry.owner_split)
            win = _shuffle_reduce(DenseWindow(carry.table), BK, BV,
                                  OFK, OFV)
            return carry._replace(table=win.table,
                                  cursor=carry.cursor + tok.shape[0])

        def fin(carry):
            keys, vals, overflow = combine_records(carry.table, spec)
            return tree_combine(keys, vals, AXIS, spec.n_procs, overflow)

        return wrap_segment_fns(mesh, spec, seg, fin)


# -- module-level aliases (pre-registry call sites) -------------------------

def run_job(spec, map_fn, mesh, tokens, task_ids, repeats):
    from repro.core.registry import get_backend
    return get_backend("2s").run_job(spec, map_fn, mesh, tokens, task_ids,
                                     repeats)


def make_segment_fns(spec, map_fn, mesh):
    from repro.core.registry import get_backend
    return get_backend("2s").make_segment_fns(spec, map_fn, mesh)
