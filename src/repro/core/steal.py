"""Device-side work stealing for the decoupled 1S engine.

The paper's claim is that decoupling pays off when "the workload per
process is unexpectedly unbalanced"; OS4M (arXiv:1406.3901) locates the
win at *operation*-level scheduling. Host re-planning at segment
boundaries (``repro.ft.straggler``) is too coarse for that — a slow rank
still gates every segment. This module moves rebalancing inside the
engine scan:

  * every scan step, each rank's executed work lands in a **progress
    row** of :class:`~repro.core.windows.EngineCarry` (``carry.work``),
    maintained with a one-hot ``psum`` — the one-sided-window analogue
    of publishing a cursor that every peer can read;
  * a **pure claim function** (:func:`claim_step`) maps that shared
    cursor state to this step's task assignment: ranks that ran ahead
    (least cumulative work) claim tasks from the *tail* of the most
    loaded rank's unstarted range;
  * because the claim is a deterministic function of replicated state,
    every rank computes the identical assignment — each task slot is
    popped from exactly one deque exactly once, so **exactly-once
    semantics hold with no dedup machinery** (same argument as the
    host re-planner, one level down).

The engine (:mod:`repro.core.onesided`) serves a claimed task to its
executor by global task id through one extra fixed-shape
``all_to_all`` per step — the one-sided "get" mirroring the push
shuffle. Results are exact regardless of who executes a task: records
are bucketized by key ownership and the Combine tree dup-sums across
every rank's window, so execution locality never changes the output.

:func:`steal_schedule` replays the same claim function on the host over
a full assignment grid — the property tests pin exactly-once on random
cursor states with it, and ``benchmarks/fig9_imbalance.py`` feeds the
realized schedule into the calibrated lockstep model.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.distributed.collectives import match_vma

# Work-unit hysteresis: a rank only claims a peer's task when the peer's
# cumulative work exceeds its own by at least this margin. One unit ==
# one compute-repeat. Strictly uniform task costs therefore never
# trigger a steal; per-task jitter above the margin causes some benign
# churn — harmless, because a steal only re-routes rows inside the
# task-fetch all_to_all the steal engine ships every step anyway, and
# results are locality-independent.
STEAL_MARGIN = 1


def claim_step(head: jnp.ndarray, tail: jnp.ndarray, work: jnp.ndarray,
               margin: int = STEAL_MARGIN) -> tuple[jnp.ndarray, ...]:
    """One scheduling round of the work-stealing claim.

    ``head``/``tail`` are the per-rank cursors into each rank's own
    unstarted column range ``[head[v], tail[v])`` (replicated: every
    rank holds the identical (P,) rows); ``work`` is the psum-maintained
    cumulative-work progress row. Executors are processed
    fastest-first (least work, ties by rank id); each either

      * pops its **own head** (the default, keeping the self-scheduled
        order), or
      * **steals the tail** of the most-loaded rank still holding
        unstarted tasks — when it has fallen ``margin`` work units
        behind that victim, or when its own range is empty, or
      * idles (src ``-1``) when every deque is empty.

    Returns ``(src_rank, src_col, head, tail)``: executor ``e`` runs the
    task at column ``src_col[e]`` of rank ``src_rank[e]``'s grid row.
    Pure and deterministic — identical on every rank for identical
    inputs, which is what makes the claims exactly-once with no dedup.
    """
    head, tail, work = (jnp.asarray(x, jnp.int32)
                        for x in (head, tail, work))
    P = head.shape[0]
    order = jnp.lexsort((jnp.arange(P), work))          # fastest first

    def assign(i, st):
        head, tail, src_r, src_c = st
        e = order[i]
        rem = tail - head
        # victim: max cumulative work among ranks with unstarted tasks
        v = jnp.argmax(jnp.where(rem > 0, work, -1))
        own = rem[e] > 0
        victim_ok = (rem[v] > 0) & (v != e)
        behind = work[v] - work[e] >= margin
        steal = victim_ok & (behind | ~own)
        take_own = own & ~steal
        src_r = src_r.at[e].set(
            jnp.where(take_own, e, jnp.where(steal, v, -1)).astype(jnp.int32))
        src_c = src_c.at[e].set(
            jnp.where(take_own, head[e],
                      jnp.where(steal, tail[v] - 1, -1)).astype(jnp.int32))
        head = head.at[e].add(take_own.astype(head.dtype))
        tail = tail.at[jnp.where(steal, v, e)].add(
            -steal.astype(tail.dtype))
        return head, tail, src_r, src_c

    idle = jnp.full((P,), -1, jnp.int32)
    # under shard_map the loop carry leaves varying wherever an input
    # varies (the progress row does), so it must enter that way too
    init = match_vma((head, tail, idle, idle), head, tail, work)
    head, tail, src_rank, src_col = lax.fori_loop(0, P, assign, init)
    return src_rank, src_col, head, tail


def segment_cursors(task_ids: jnp.ndarray, axis: str | None = None):
    """Initial (head, tail) rows for one segment grid.

    ``tail`` counts each rank's *real* columns (padding id ``-1`` is
    excluded from the deques — a fast rank steals work instead of
    running a no-op). On device, pass ``axis`` to build the replicated
    row from each rank's local count via the one-hot psum; on host,
    pass the full (P, n) grid with ``axis=None``.
    """
    if axis is None:
        ids = jnp.asarray(task_ids)
        tail = (ids >= 0).sum(axis=1).astype(jnp.int32)
        return jnp.zeros_like(tail), tail
    me = lax.axis_index(axis)
    P = lax.psum(1, axis)
    count = (jnp.asarray(task_ids) >= 0).sum().astype(jnp.int32)
    tail = lax.psum(jnp.where(jnp.arange(P) == me, count, 0), axis)
    return jnp.zeros_like(tail), tail


def compact_columns(task_ids: jnp.ndarray):
    """Permutation putting a grid row's real columns before its padding
    (``claim_step`` addresses each deque as a dense ``[0, count)``
    range). Stable, so the self-scheduled order is preserved."""
    return jnp.argsort(jnp.asarray(task_ids) < 0)


# ---------------------------------------------------------------------------
# fleet-wide cursor — composite (job, task) grids for cross-job stealing
# ---------------------------------------------------------------------------
#
# ``claim_step`` itself is already fleet-ready: it schedules over opaque
# deque columns, so feeding it a grid whose columns come from SEVERAL
# jobs turns intra-job stealing into global work stealing with the same
# pure/replicated/exactly-once argument (each composite column is still
# popped exactly once). What the fleet adds is the *encoding*: a
# composite task id ``slot * stride + local_id`` names (member job,
# task), and :func:`fleet_merge` lays the members' columns out per rank
# with a job-priority lane ordering — the shared cursor every rank's
# claims draw from. :func:`composite_slots` inverts the encoding.

def composite_slots(task_ids, stride: int):
    """Member-job slot of each composite task id (-1 for padding)."""
    ids = np.asarray(task_ids, np.int64)
    return np.where(ids >= 0, ids // int(stride), -1).astype(np.int32)


def fleet_merge(task_ids, repeats, *, stride: int,
                priorities=None) -> tuple[np.ndarray, np.ndarray]:
    """Merge K member assignment grids into one fleet grid.

    ``task_ids`` / ``repeats`` are parallel sequences of (P, T_j) member
    grids (padding id -1, any T_j). Member ``j``'s local ids are lifted
    to composite ids ``j * stride + local``; per rank the columns are
    ordered by **priority lane** (higher ``priorities[j]`` first, stable
    in member order within a tie) and round-robin interleaved across the
    members of a lane — co-resident equal-priority jobs progress
    together, while a higher lane's tasks sit at the head of every
    deque so they are claimed (and stolen) first. Returns ``(ids,
    reps)`` of shape (P, N), -1/1 padded.

    A single-member merge is the identity (ids unchanged, order
    preserved) — the single-job fleet reduces bit-identically to the
    solo schedule, which the property tests pin.
    """
    K = len(task_ids)
    assert K == len(repeats) and K >= 1
    stride = int(stride)
    prios = ([0] * K if priorities is None else list(priorities))
    assert len(prios) == K
    grids = [np.asarray(g, np.int32) for g in task_ids]
    rgrids = [np.asarray(r, np.int32) for r in repeats]
    P = grids[0].shape[0]
    for g, r in zip(grids, rgrids):
        assert g.shape == r.shape and g.shape[0] == P, \
            "member grids must share the rank count"
        assert g.max(initial=-1) < stride, \
            f"member local ids must fit the stride ({stride})"
    # lanes: higher priority first, admission (member) order within
    lanes: dict[int, list[int]] = {}
    for j in sorted(range(K), key=lambda j: (-prios[j], j)):
        lanes.setdefault(prios[j], []).append(j)
    rows_ids: list[list[int]] = [[] for _ in range(P)]
    rows_reps: list[list[int]] = [[] for _ in range(P)]
    for r in range(P):
        for prio in sorted(lanes, reverse=True):
            members = lanes[prio]
            cols = [[(int(t), int(rep)) for t, rep in
                     zip(grids[j][r], rgrids[j][r]) if t >= 0]
                    for j in members]
            width = max((len(c) for c in cols), default=0)
            for k in range(width):        # round-robin interleave
                for j, c in zip(members, cols):
                    if k < len(c):
                        t, rep = c[k]
                        rows_ids[r].append(j * stride + t)
                        rows_reps[r].append(rep)
    N = max((len(row) for row in rows_ids), default=0)
    ids = np.full((P, max(N, 1)), -1, np.int32)
    reps = np.ones((P, max(N, 1)), np.int32)
    for r in range(P):
        ids[r, : len(rows_ids[r])] = rows_ids[r]
        reps[r, : len(rows_reps[r])] = rows_reps[r]
    return ids, reps


# ---------------------------------------------------------------------------
# host replay — the same claim function, driven over a whole grid
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StealSchedule:
    """The realized execution schedule of one segment under stealing."""
    src_rank: np.ndarray     # (P, n) rank whose slot step k executed (-1 idle)
    src_col: np.ndarray      # (P, n) column within the source rank's row
    exec_ids: np.ndarray     # (P, n) global task id executed (-1 idle)
    exec_reps: np.ndarray    # (P, n) compute-repeats executed (0 idle)
    work: np.ndarray         # (P,) final cumulative work row
    stolen: np.ndarray       # (P,) tasks each rank executed for a peer
    slot_work: np.ndarray | None = None
                             # (coslots,) executed work per member-job
                             #   slot when replaying a composite fleet
                             #   grid — the host twin of the engine's
                             #   psum-maintained ``carry.job_work`` row

    @property
    def n_stolen(self) -> int:
        return int(self.stolen.sum())


@lru_cache(maxsize=None)
def _jitted_claim(margin: int):
    """One compiled claim program per margin, shared by every
    steal_schedule call (the jit cache is keyed on the callable, so a
    fresh partial per call would re-trace every time)."""
    return jax.jit(partial(claim_step, margin=margin))


def steal_schedule(task_ids: np.ndarray, repeats: np.ndarray,
                   margin: int = STEAL_MARGIN,
                   work0: np.ndarray | None = None,
                   coslots: int = 1,
                   costride: int = 0) -> StealSchedule:
    """Replay :func:`claim_step` over one (P, n) assignment grid.

    This is bit-identical to the schedule the device scan realizes (it
    is the same jitted claim function, fed the same replicated state),
    which is what lets the benchmark model a steal run's makespan and
    the tests check exactly-once without touching the engine.
    ``work0`` seeds the progress row (cumulative across segments).

    For a composite fleet grid (:func:`fleet_merge`), pass the domain's
    ``coslots``/``costride`` to also get ``slot_work`` — executed work
    split by member-job slot, matching ``carry.job_work`` on device.
    """
    ids = np.asarray(task_ids, np.int32)
    reps = np.asarray(repeats, np.int32)
    assert ids.shape == reps.shape
    P, n = ids.shape
    # per-rank compaction: real columns first, as the engine sees them
    perm = np.argsort(ids < 0, axis=1, kind="stable")
    cids = np.take_along_axis(ids, perm, axis=1)
    creps = np.take_along_axis(reps, perm, axis=1)
    head = np.zeros((P,), np.int32)
    tail = (ids >= 0).sum(axis=1).astype(np.int32)
    work = (np.zeros((P,), np.int32) if work0 is None
            else np.asarray(work0, np.int32).copy())
    step = _jitted_claim(margin)
    src_rank = np.full((P, n), -1, np.int32)
    src_col = np.full((P, n), -1, np.int32)
    exec_ids = np.full((P, n), -1, np.int32)
    exec_reps = np.zeros((P, n), np.int32)
    stolen = np.zeros((P,), np.int32)
    for k in range(n):
        sr, sc, h, t = (np.asarray(x) for x in step(
            jnp.asarray(head), jnp.asarray(tail), jnp.asarray(work)))
        head, tail = h.astype(np.int32), t.astype(np.int32)
        live = sr >= 0
        src_rank[:, k], src_col[:, k] = sr, sc
        exec_ids[live, k] = cids[sr[live], sc[live]]
        exec_reps[live, k] = creps[sr[live], sc[live]]
        work = work + exec_reps[:, k]
        stolen += (live & (sr != np.arange(P))
                   & (exec_ids[:, k] >= 0)).astype(np.int32)
    if coslots > 1:
        assert costride > 0, "composite replay needs the domain stride"
        slot_work = np.zeros((coslots,), np.int64)
        done = exec_ids >= 0
        np.add.at(slot_work, exec_ids[done] // costride,
                  exec_reps[done].astype(np.int64))
    else:
        slot_work = np.asarray([int(exec_reps.sum())], np.int64)
    return StealSchedule(src_rank, src_col, exec_ids, exec_reps,
                         work, stolen, slot_work)
