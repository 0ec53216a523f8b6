"""Re-mesh a checkpointed job onto a different process count — exactly.

The elastic pivot of the fleet subsystem: a snapshot taken by a P_old
fleet is folded onto P_new surviving ranks and resumed mid-stream, and
the resumed job's records are identical to an unfailed run. Three
properties of the framework make that a theorem rather than a hope:

  * Combine dup-sums records by key across ranks (ownership-transfer
    semantics, paper footnote 2) — ANY redistribution of the per-rank
    dense windows is exact, so ``r_old % P_new`` round-robin folding is
    as good as any;
  * task ids are global (``plan.file_offset = id * task_size`` is
    P-independent) and the planner is decentralized, so re-bucketizing
    the not-yet-executed assignment is pure arithmetic
    (:func:`repro.ft.elastic.rebucketize_tasks`);
  * the owner map is carry *data*, so folding it (``owner % P_new``) and
    clipping split widths re-targets the reduce side without recompiling
    anything the new mesh would not have compiled anyway.

The fold itself runs on the NEW mesh as a tiny SPMD program
(:func:`fold_program`): each surviving rank sums its group of old
windows with ``sat_add_i32`` (the engine's saturating adds — folding
near-full int32 count tables must saturate, not wrap) and the program
emits a psum checksum of the folded fleet. The host verifies it against
the independent numpy twin (:func:`repro.ft.elastic.fold_windows`,
int64-accumulate-then-clip) before the job resumes — a disagreement
means a real fold bug and raises :class:`RemeshChecksumError` instead
of silently resuming with corrupt windows. The program ships through
fleetlint like every engine program (:func:`remesh_program_handles`).
"""
from __future__ import annotations

import numpy as np

from repro.core.kv import KEY_SENTINEL
from repro.core.partition import fold_owner_map, hash_owner_map
from repro.core.windows import AXIS, EngineCarry
from repro.ft.elastic import fold_windows, rebucketize_tasks

I32_MASK = 0xFFFFFFFF


class RemeshChecksumError(RuntimeError):
    """The device fold and the host numpy twin disagree on the folded
    windows — the re-meshed job would resume from corrupt state, so the
    restore refuses. This is a framework bug (the two folds are
    independent implementations of the same sum), not a user error."""


def _wrap_i32_sum(a) -> int:
    """int32 wrap-around sum of an array — the checksum both sides
    compute (two's complement, so numpy int64 mod 2^32 matches XLA's
    int32 accumulation bit-for-bit)."""
    s = int(np.asarray(a, np.int64).sum()) & I32_MASK
    return s - (1 << 32) if s >= (1 << 31) else s


# -- the device fold program -------------------------------------------------

_PROGRAMS: dict = {}


def fold_program(mesh, n_old: int, vocab: int):
    """Compiled SPMD fold on the NEW mesh: (grouped old windows, owner
    map, owner split) -> (folded windows, folded map, clipped split,
    psum checksum).

    Inputs are host-grouped by destination: ``groups[(r % P_new),
    (r // P_new)] = window[r]`` — shape (P_new, G, vocab) with ``G =
    ceil(P_old / P_new)`` and zero padding, so each surviving rank sums
    exactly its own group with the engine's saturating adds. The owner
    map/split rows are replicated (every rank holds the same row); the
    elementwise ``% P_new`` / clip preserves that, and the checksum is
    psum-replicated — the replication contract fleetlint's REP001
    checks on this very program."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.core.combine import sat_add_i32
    from jax import shard_map

    n_new = int(mesh.devices.size)
    G = -(-int(n_old) // n_new)
    key = (mesh, n_old, vocab)
    if key in _PROGRAMS:
        return _PROGRAMS[key]

    def body(groups, om, osplit):
        # groups: (1, G, vocab) per shard — ascending g matches the host
        # twin's accumulation order (saturating adds of non-negative
        # counts are order-independent anyway)
        t = groups[0, 0]
        for g in range(1, G):
            t = sat_add_i32(t, groups[0, g])
        om_new = jnp.mod(om, jnp.int32(n_new))
        os_new = jnp.clip(osplit, jnp.int32(1), jnp.int32(n_new))
        csum = lax.psum(jnp.sum(t, dtype=jnp.int32), AXIS)
        return t[None], om_new, os_new, csum[None]

    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS)),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS))))
    _PROGRAMS[key] = fn
    return fn


def remesh_program_handles(mesh, n_old: int | None = None,
                           vocab: int = 64) -> list:
    """The fold program as fleetlint :class:`ProgramHandle`\\ s — the
    re-mesh path ships through the same static analysis as the engines
    (REP001 proves the folded owner map/split and the checksum really
    are replicated; SPMD001 that the fold only touches ``procs``)."""
    import jax
    import jax.numpy as jnp

    from repro.core.registry import ProgramHandle

    n_new = int(mesh.devices.size)
    if n_old is None:
        n_old = 2 * n_new        # a genuine shrink: G = 2
    G = -(-int(n_old) // n_new)
    fn = fold_program(mesh, n_old, vocab)
    args = (jax.ShapeDtypeStruct((n_new, G, vocab), jnp.int32),
            jax.ShapeDtypeStruct((n_new, vocab), jnp.int32),
            jax.ShapeDtypeStruct((n_new, vocab), jnp.int32))
    return [ProgramHandle(
        name=f"fleet/remesh/fold[{n_old}->{n_new}]",
        fn=fn, args=args,
        arg_paths=("tables", "owner_map", "owner_split"),
        out_paths=("table", "owner_map", "owner_split", "checksum"),
        replicated_in=("owner_map", "owner_split"),
        replicated_out=("owner_map", "owner_split", "checksum"),
        allowed_axes=(AXIS,))]


# -- host orchestration ------------------------------------------------------

def _zeros_like_carry() -> EngineCarry:
    """Structure/dtype-only template for ``CheckpointManager.restore``
    (leaf shapes come from the npz, so one scalar template restores a
    snapshot taken at ANY process count)."""
    return EngineCarry(*(np.zeros((), np.int32)
                         for _ in EngineCarry._fields))


def _fold_pending(carry: EngineCarry) -> np.ndarray:
    """Old per-rank windows with the in-flight ``pending_*`` chunks
    folded in, int32-saturated — the complete record of every executed
    task. Accumulates in int64 then clips, exactly what the engine's
    ``sat_add_i32`` would have produced had it drained the chunk
    (non-negative counts)."""
    table = np.asarray(carry.table)
    P_old = table.shape[0]
    acc = table.astype(np.int64)
    pk = np.asarray(carry.pending_k).reshape(P_old, -1)
    pv = np.asarray(carry.pending_v).reshape(P_old, -1)
    for r in range(P_old):
        valid = pk[r] != int(KEY_SENTINEL)
        np.add.at(acc[r], pk[r][valid], pv[r][valid].astype(np.int64))
    i32 = np.iinfo(np.int32)
    return np.clip(acc, i32.min, i32.max).astype(np.int32)


def _check_compat(handle, found: int, extra: dict):
    """The same snapshot-compatibility guards as ``JobHandle.restore``
    — a cross-P fold cannot paper over a backend/stealing/partitioner
    mismatch any more than a same-P restore can."""
    saved = extra.get("backend")
    if saved is not None and saved != handle.backend.name:
        raise ValueError(
            f"checkpoint step {found} was taken by backend {saved!r} — "
            f"it cannot elastic-restore into a {handle.backend.name!r} "
            f"handle; resubmit with JobConfig(backend={saved!r})")
    saved_steal = extra.get("stealing")
    if (saved_steal is not None
            and bool(saved_steal) != handle.config.stealing):
        raise ValueError(
            f"checkpoint step {found} was taken with "
            f"stealing={bool(saved_steal)} — resubmit with "
            f"JobConfig(stealing={bool(saved_steal)})")
    saved_part = extra.get("partitioner")
    if saved_part is not None and saved_part != handle.spec.partitioner:
        raise ValueError(
            f"checkpoint step {found} was taken with "
            f"partitioner={saved_part!r} — resubmit with "
            f"JobConfig(partitioner={saved_part!r})")


def elastic_restore(handle, manager, step: int | None = None):
    """Resume a snapshot taken at ANY process count into ``handle``
    (which runs at ``handle.spec.n_procs`` — the NEW mesh).

    Same-P snapshots take the ordinary seek-and-restore path. Cross-P
    snapshots are folded: pending chunks into the windows (host), old
    windows/owner maps onto the new ranks (device program on the new
    mesh, checksum-verified against the numpy twin), and the
    not-yet-executed tasks re-bucketized round-robin — then installed
    via :meth:`JobHandle.elastic_load`. No input read is replayed in
    either path; exactness is the module-docstring argument.

    Returns the handle."""
    found, extra = manager.peek(step)
    _check_compat(handle, found, extra)
    P_new = handle.spec.n_procs
    _, carry, extra = manager.restore(_zeros_like_carry(), step=found)
    P_old = int(np.asarray(carry.table).shape[0])
    if P_old == P_new:
        return handle.restore(manager, step=found)

    tables = _fold_pending(carry)                    # (P_old, vocab)
    vocab = tables.shape[1]
    G = -(-P_old // P_new)
    groups = np.zeros((P_new, G, vocab), np.int32)
    for r in range(P_old):
        groups[r % P_new, r // P_new] = tables[r]

    if handle.spec.partitioner == "hash":
        # the hash rule is P-dependent: folding the OLD map % P_new
        # would skew ownership, so feed the fresh P_new rule through the
        # program (its % P_new is then the identity)
        om = hash_owner_map(vocab, P_new)
        osplit = np.ones((vocab,), np.int32)
    else:
        # sampled maps reflect the data's skew, which did not change —
        # fold them (the host twin of the device's % / clip)
        om, osplit = fold_owner_map(
            np.asarray(carry.owner_map)[0],
            np.asarray(carry.owner_split)[0], P_new)
    om = np.ascontiguousarray(
        np.broadcast_to(np.asarray(om, np.int32), (P_new, vocab)))
    osplit = np.ascontiguousarray(
        np.broadcast_to(np.asarray(osplit, np.int32), (P_new, vocab)))

    fn = fold_program(handle.mesh, P_old, vocab)
    table_new, om_new, os_new, csum = fn(groups, om, osplit)
    got = int(np.asarray(csum)[0])
    want = _wrap_i32_sum(fold_windows(tables, P_new))
    if got != want:
        raise RemeshChecksumError(
            f"device fold checksum {got} != host twin {want} folding "
            f"{P_old} -> {P_new} ranks (vocab={vocab}) — refusing to "
            "resume from corrupt windows")

    ids, reps = rebucketize_tasks(
        np.asarray(extra["task_ids"], np.int32),
        np.asarray(extra["repeats"], np.int32),
        int(extra["cursor"]), P_new)
    return handle.elastic_load(np.asarray(table_new),
                               np.asarray(om_new)[0],
                               np.asarray(os_new)[0], ids, reps)
