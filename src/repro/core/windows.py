"""Window abstractions — the JAX analogue of the paper's MPI windows.

The paper allocates four windows per process: Status, Key-Value, Combine and
Displacement. On TPU these become preallocated device-resident arrays carried
through the engine's scan:

  * ``DenseWindow``   — the Key-Value window for bounded key spaces
                        (wordcount over a known vocab): a dense accumulation
                        table indexed by key. Remote "puts" land here via the
                        chunked push shuffle.
  * ``LogWindow``     — the Key-Value window for two-lane keys (a
                        (word, document) pair, too wide for a dense
                        table): a log each step appends its reduced
                        records to, compacted once by one sort at finish.
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
from jax import lax

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


class LogWindow(NamedTuple):
    """Key-Value window for two-lane keys: a log of (major, minor, value)
    records, KEY_SENTINEL in the major lane marking an empty slot.

    Each engine step appends its records at its own slots, so nothing is
    merged per step and no record is lost: the log holds one region of
    :func:`log_width` slots for every step a rank runs. :func:`compact_log`
    sums duplicate keys once, at finish."""
    major: jnp.ndarray          # (slots,) int32
    minor: jnp.ndarray          # (slots,) int32
    values: jnp.ndarray         # (slots,)

    @staticmethod
    def alloc(slots: int) -> LogWindow:
        return LogWindow(jnp.full((slots,), KEY_SENTINEL, jnp.int32),
                         jnp.full((slots,), KEY_SENTINEL, jnp.int32),
                         jnp.zeros((slots,), jnp.int32))

    def append(self, offset, major, minor, values) -> LogWindow:
        """Write one step's records at slot ``offset``."""
        return LogWindow(*(lax.dynamic_update_slice(x, y, (offset,))
                           for x, y in zip(self, (major, minor, values))))


def log_width(spec) -> int:
    """Log slots one engine step appends for two-lane keys: the task's
    reduced records (one slot per token) and, at P > 1, the chunk of
    pushed records received one step earlier."""
    P, S = spec.n_procs, spec.task_size
    return S if P == 1 else P * spec.push_cap + S


def status_vector(n_procs: int) -> jnp.ndarray:
    return jnp.full((n_procs,), STATUS_INIT, jnp.int32)


# ---------------------------------------------------------------------------
# the engine carry (Status + Key-Value + in-flight chunk windows)
# ---------------------------------------------------------------------------

class EngineCarry(NamedTuple):
    table: jnp.ndarray       # Key-Value window: dense (vocab,), or a
                             #   LogWindow for two-lane keys
    pending_k: jnp.ndarray   # in-flight received chunk (P, cap); a
                             #   (major, minor) pair for two-lane keys
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
    if spec.key_lanes == 2:
        table = LogWindow.alloc(spec.log_steps * log_width(spec))
        pending_k = (jnp.full((P, cap), KEY_SENTINEL, jnp.int32),) * 2
    else:
        table = jnp.zeros((spec.vocab,), jnp.int32)
        pending_k = jnp.full((P, cap), KEY_SENTINEL, jnp.int32)
    return pvary(EngineCarry(
        table=table,
        pending_k=pending_k,
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


def compact_log(carry: EngineCarry, spec):
    """A rank's log (and, at P > 1, the chunk still in flight) -> sorted
    records entering the Combine tree: one sort on (major, minor), run
    sums, the records compacted into ``spec.combine_capacity`` slots.

    Returns ``(keys, vals, overflow, appended)``: keys as a (major, minor)
    pair; ``overflow`` the records lost to a Combine width below the
    number of distinct keys, as ``combine_records`` counts it;
    ``appended`` the records the log received."""
    from repro.core.kv import sort_reduce
    with jax.named_scope("compact"):
        major, minor, values = carry.table
        if spec.n_procs > 1:
            major, minor = (jnp.concatenate([x, p.reshape(-1)]) for x, p
                            in zip((major, minor), carry.pending_k))
            values = jnp.concatenate([values, carry.pending_v.reshape(-1)])
        appended = jnp.sum(major != KEY_SENTINEL, dtype=jnp.int32)
        W = spec.combine_capacity
        keys, vals, n_unique = sort_reduce((major, minor), values, W)
        overflow = jnp.maximum(n_unique - W, 0)
    return keys, vals, overflow, appended


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
        return jax.tree.map(lambda x: x[None],
                            fin_body(jax.tree.map(lambda x: x[0], c)))

    seg_sm = jax.jit(shard_map(
        mr_segment, mesh=mesh,
        in_specs=(carry_specs, spec_p, spec_p, spec_p),
        out_specs=carry_specs))
    fin_sm = jax.jit(shard_map(
        mr_finish, mesh=mesh, in_specs=(carry_specs,), out_specs=spec_p))
    init_sm = jax.jit(shard_map(
        mr_init, mesh=mesh, in_specs=(), out_specs=carry_specs))
    return init_sm, seg_sm, fin_sm
