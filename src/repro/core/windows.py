"""Window abstractions — the JAX analogue of the paper's MPI windows.

The paper allocates four windows per process: Status, Key-Value, Combine and
Displacement. On TPU these become preallocated device-resident arrays carried
through the engine's scan:

  * ``DenseWindow``   — the Key-Value window for bounded key spaces
                        (wordcount over a known vocab): a dense accumulation
                        table indexed by key. Remote "puts" land here via the
                        chunked push shuffle.
  * ``SortedWindow``  — the generic (unbounded keys) Key-Value window: a
                        log-structured sorted-run table, merged incrementally.
  * ``status``        — per-process phase/task cursor vector (observability,
                        checkpoint manifest, ownership-transfer bookkeeping).
  * fill ``counts``   — play the Displacement window's role (where the next
                        record lands per bucket).

STATUS codes mirror the paper's (e.g. ``STATUS_REDUCE``).

``EngineCarry`` — the windows as carried through an engine's scan — lives
here too, shared by every backend so the checkpoint / fault-tolerance
layers see one snapshot type regardless of engine (the MR-2S segmented
path simply leaves the in-flight ``pending_*`` buffers empty).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.kv import KEY_SENTINEL

AXIS = "procs"

STATUS_INIT = 0
STATUS_MAP = 1
STATUS_REDUCE = 2
STATUS_COMBINE = 3
STATUS_DONE = 4


class DenseWindow(NamedTuple):
    """Dense Key-Value window: ``table[k]`` accumulates the value for key k
    owned by this process (non-owned slots stay 0)."""
    table: jnp.ndarray          # (vocab,) value dtype

    @staticmethod
    def alloc(vocab: int, dtype=jnp.int32) -> DenseWindow:
        return DenseWindow(jnp.zeros((vocab,), dtype))

    def put(self, keys, values) -> DenseWindow:
        """Fold a chunk of records (the receive side of a one-sided put)."""
        valid = keys != KEY_SENTINEL
        idx = jnp.where(valid, keys, 0)
        return DenseWindow(self.table.at[idx].add(jnp.where(valid, values, 0)))

    def to_records(self, my_rank, n_procs):
        """Sorted unique (key, value) records owned by this process."""
        keys = jnp.arange(self.table.shape[0], dtype=jnp.int32)
        valid = self.table != 0
        return jnp.where(valid, keys, KEY_SENTINEL), jnp.where(valid, self.table, 0)


class SortedWindow(NamedTuple):
    """Generic Key-Value window: sorted unique runs, merged on arrival."""
    keys: jnp.ndarray           # (capacity,) int32, KEY_SENTINEL padded
    values: jnp.ndarray         # (capacity,)

    @staticmethod
    def alloc(capacity: int, dtype=jnp.int32) -> SortedWindow:
        return SortedWindow(
            jnp.full((capacity,), KEY_SENTINEL, jnp.int32),
            jnp.zeros((capacity,), dtype),
        )

    def put(self, keys, values) -> SortedWindow:
        from repro.core.kv import merge_sorted
        k, v = merge_sorted(self.keys, self.values, keys, values,
                            self.keys.shape[0])
        return SortedWindow(k, v)


def status_vector(n_procs: int) -> jnp.ndarray:
    return jnp.full((n_procs,), STATUS_INIT, jnp.int32)


# ---------------------------------------------------------------------------
# the engine carry (Status + Key-Value + in-flight chunk windows)
# ---------------------------------------------------------------------------

class EngineCarry(NamedTuple):
    table: jnp.ndarray       # dense Key-Value window (vocab,)
    pending_k: jnp.ndarray   # in-flight received chunk (P, cap)
    pending_v: jnp.ndarray
    status: jnp.ndarray      # scalar per process (STATUS_*)
    cursor: jnp.ndarray      # tasks completed (restart point)
    # work-stealing claim state (core/steal.py): psum-maintained progress
    # rows, replicated on every rank. ``work`` is cumulative executed
    # compute-repeats per rank; ``stolen`` counts tasks a rank executed
    # for a peer. Engines without stealing leave both at zero; the rows
    # ride the carry so checkpoints capture mid-job claim state for free.
    work: jnp.ndarray        # (P,) int32 progress row
    stolen: jnp.ndarray      # (P,) int32 steal counters
    # cross-job co-scheduling (core/workdomain.py): executed work per
    # member job *slot*, psum-maintained like ``work``. Solo jobs carry
    # a single always-zero slot (coslots == 1 skips the update — zero
    # overhead on the solo path); a WorkDomain reads the deltas to
    # charge each tenant for work actually EXECUTED in a mixed slice.
    job_work: jnp.ndarray    # (coslots,) int32 executed work per job
    # reduce-side partitioning state (core/partition.py): the dense
    # key→owner map and per-key replica counts, replicated per rank.
    # Riding the carry (not the jitted program) means one compiled
    # engine serves every owner map, and a checkpoint snapshots the
    # map for free — restore resumes with the exact assignment that
    # produced the windows.
    owner_map: jnp.ndarray   # (vocab,) int32 key -> base owner rank
    owner_split: jnp.ndarray  # (vocab,) int32 replicas per key (>= 1)


def init_carry(spec) -> EngineCarry:
    from repro.core.kv import owner_of
    from repro.distributed.collectives import pvary
    P, cap = spec.n_procs, spec.push_cap
    return pvary(EngineCarry(
        table=jnp.zeros((spec.vocab,), jnp.int32),
        pending_k=jnp.full((P, cap), KEY_SENTINEL, jnp.int32),
        pending_v=jnp.zeros((P, cap), jnp.int32),
        status=jnp.int32(STATUS_MAP),
        cursor=jnp.int32(0),
        work=jnp.zeros((P,), jnp.int32),
        stolen=jnp.zeros((P,), jnp.int32),
        job_work=jnp.zeros((getattr(spec, "coslots", 1) or 1,),
                           jnp.int32),
        # the hash rule as a dense map — bit-identical to owner_of, and
        # the seed a skew-aware partitioner overwrites before step 0
        owner_map=owner_of(jnp.arange(spec.vocab, dtype=jnp.int32), P),
        owner_split=jnp.ones((spec.vocab,), jnp.int32),
    ), AXIS)


def combine_records(table: jnp.ndarray, spec):
    """Window -> sorted records entering the Combine tree, honoring
    ``spec.combine_capacity`` identically in every backend and mode.

    Returns ``(keys, vals, overflow)``: ``overflow`` counts the records
    this rank *lost* squeezing its window into the Combine width W (0
    whenever W covers the window — truncation is never silent)."""
    from repro.core.kv import local_reduce
    with jax.named_scope("combine"):
        keys, vals = DenseWindow(table).to_records(None, spec.n_procs)
        W = spec.combine_capacity
        overflow = jnp.int32(0)
        if W != keys.shape[0]:
            keys, vals, n_unique = local_reduce(keys, vals, W)
            overflow = jnp.maximum(n_unique.astype(jnp.int32) - W, 0)
    return keys, vals, overflow


def wrap_segment_fns(mesh, spec, seg_body, fin_body):
    """Lift per-shard segment bodies into jitted shard_map fns.

    ``seg_body(carry, tok, tid, rep)`` and ``fin_body(carry)`` operate on
    the un-sharded (per-device) view; the returned
    ``(init_fn, segment_fn, finish_fn)`` operate on host arrays with a
    leading shard dimension — the shape every backend's segmented path
    shares, so the ckpt/ft layers are backend-agnostic.
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    spec_p = P(AXIS)
    carry_specs = EngineCarry(*([spec_p] * len(EngineCarry._fields)))

    # named functions, so the compiled modules read jit_mr_init,
    # jit_mr_segment and jit_mr_finish in a device trace
    def mr_init():
        # broadcast per-shard carry: every leaf gains a leading shard dim
        return jax.tree.map(lambda x: x[None], init_carry(spec))

    def mr_segment(c, t, i, r):
        return jax.tree.map(
            lambda x: x[None],
            seg_body(jax.tree.map(lambda x: x[0], c), t[0], i[0], r[0]))

    def mr_finish(c):
        return tuple(
            x[None] for x in fin_body(jax.tree.map(lambda x: x[0], c)))

    seg_sm = jax.jit(shard_map(
        mr_segment, mesh=mesh,
        in_specs=(carry_specs, spec_p, spec_p, spec_p),
        out_specs=carry_specs,
        # a pallas kernel body does not trace under the varying-axes check
        check_vma=not spec.fused_map))
    fin_sm = jax.jit(shard_map(
        mr_finish, mesh=mesh, in_specs=(carry_specs,),
        out_specs=(spec_p, spec_p, spec_p)))
    init_sm = jax.jit(shard_map(
        mr_init, mesh=mesh, in_specs=(), out_specs=carry_specs))
    return init_sm, seg_sm, fin_sm
