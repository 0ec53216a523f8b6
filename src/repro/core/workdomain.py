"""WorkDomain — cross-job operation-level co-scheduling (OS4M direction).

The decoupled engine stops idling *ranks* inside a job (core/steal.py);
this module stops idling them at the job boundary: K admitted jobs that
share one compiled program (asserted at admission since the scheduler
landed) merge into ONE composite engine program, so a rank drained by
job A's tail executes job B's tasks *in the same device step* — global
work stealing at operation granularity, per OS4M (arXiv:1406.3901).

The merge is an encoding, not new engine machinery:

  * **composite task ids** — member job ``j``'s task ``t`` becomes
    ``j * costride + t`` (:func:`repro.core.steal.fleet_merge` lays the
    members' columns into one fleet grid, priority lanes first,
    round-robin within a lane — the shared cursor every rank's claims
    draw from). A :class:`~repro.data.source.FleetSource` places member
    ``j``'s bytes at element ``j * costride * task_size``, so the
    ordinary ``plan.file_offset`` addresses any member's task — the
    feed, the prefetcher and the engine's steal fetch all serve
    cross-job reads unchanged.
  * **composite keys** — the engine offsets every emitted key by
    ``slot * (vocab // coslots)`` into the owning job's disjoint window
    slice (``repro.core.onesided._step``), so bucketize/combine/fold
    route each record to its job's windows and per-job dup-sum
    exactness follows from the solo argument, window by window. Every
    member's records are bit-identical to its solo run, wherever
    stealing executed its tasks.
  * **executed-work row** — ``carry.job_work`` (one psum-maintained
    slot per member) tells the scheduler how much of each tenant's work
    actually ran in a mixed slice, so fair share charges execution, not
    assignment.

Why the domain can beat K solo-sliced jobs: a solo segment of width 1
has one task per rank — nothing to steal inside the step. The domain
packs ``pack`` members' columns into each segment, so the in-scan claim
function balances across job boundaries at task granularity; under
imbalanced per-job tails the merged segment's makespan approaches the
mean load instead of the max (benchmarks/fig14_crossjob.py).

Members finalize independently: as soon as the shared cursor has
consumed all of member ``j``'s columns, the (pure) finish program runs
on the current carry, the composite records are split by key range and
the member's :class:`~repro.core.job.JobResult` is adopted by its
handle — a short job co-scheduled with a long one still finishes early.
``work_per_rank`` on a member result reports its *assigned* per-rank
work (per-member×per-rank execution is intentionally not tracked — the
domain-level split lives on the domain handle's carry rows).

Eligibility (:func:`can_coschedule`): segmented '1s' jobs sharing
(backend, JobSpec, map_fn) with a non-sampling partitioner and no
fused_map — the fused kernel resolves owners in-kernel over the solo
key space, so fused jobs cleanly fall back to solo slicing.

Checkpoint/restore: the domain checkpoints ONCE through the ordinary
:meth:`~repro.core.job.JobHandle.checkpoint` — the snapshot carries the
composite carry plus the shared fleet cursor and merged grids, so a
mid-co-schedule restore resumes record-identically. The scheduler
records domain membership in the fleet manifest and re-forms domains
deterministically before restoring them.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import steal
from repro.core.job import JobHandle, JobResult
from repro.core.kv import KEY_SENTINEL
from repro.core.planner import TaskPlan
from repro.core.records import Records
from repro.core.usecase import finalize
from repro.core.windows import AXIS
from repro.data.feed import SegmentFeed
from repro.data.source import FleetSource


def coschedule_key(handle: JobHandle) -> tuple:
    """Program-compatibility key: jobs sharing it can merge into one
    WorkDomain (the same key the scheduler's jit-memo assert uses)."""
    return (handle.backend.name, handle.spec, id(handle._map_fn))


def can_coschedule(handle: JobHandle) -> bool:
    """Whether this job may join a WorkDomain. Fused/coded jobs and
    sampling partitioners cleanly reject (solo slicing instead): the
    fused kernel has no composite-key path, the coded exchange's r-group
    decode has no fleet-cursor claim granularity, and a sampled owner
    map is built per-job over the solo key space; two-lane keys have no
    composite key range to offset into."""
    spec = handle.spec
    return (getattr(handle.backend, "supports_coschedule", False)
            and spec.coslots == 1
            and not spec.fused_map
            and spec.code_rate == 1
            and spec.key_lanes == 1
            and not handle.partitioner.needs_sample
            and handle.config.segment > 0
            and handle.cursor == 0
            and handle._carry is None
            and handle._result is None)


class WorkDomain:
    """K program-compatible jobs fused into one co-scheduled engine run.

    ``handles`` must all satisfy :func:`can_coschedule` and share
    :func:`coschedule_key`. ``pack`` is how many member segments one
    domain segment packs (default: K — every live member contributes a
    column per step); ``stride`` overrides the computed task-id stride
    (checkpoint re-formation passes the recorded one).
    """

    def __init__(self, handles: list[JobHandle], *, names=None,
                 priorities=None, mesh=None, pack: int | None = None,
                 stride: int | None = None, feed_budget=None):
        if len(handles) < 2:
            raise ValueError("a WorkDomain needs at least two member "
                             "jobs (one job co-schedules with nobody)")
        key0 = coschedule_key(handles[0])
        for h in handles:
            if not can_coschedule(h):
                raise ValueError(
                    "job is not co-schedulable (backend without "
                    "supports_coschedule, fused_map, code_rate > 1, "
                    "two-lane keys, "
                    "sampling partitioner, oneshot, or already started)")
            if coschedule_key(h) != key0:
                raise ValueError(
                    "WorkDomain members must share one compiled program "
                    f"(backend, JobSpec, use-case): {coschedule_key(h)} "
                    f"!= {key0}")
        self.members = list(handles)
        self.names = (list(names) if names is not None
                      else [f"member-{j}" for j in range(len(handles))])
        assert len(self.names) == len(self.members)
        self.priorities = (list(priorities) if priorities is not None
                           else [0] * len(self.members))
        self.K = len(self.members)
        spec0 = self.members[0].spec
        cfg0 = self.members[0].config
        need = max(h.plan.n_tasks for h in self.members)
        self.stride = int(stride) if stride is not None else need
        if self.stride < need:
            raise ValueError(f"stride {self.stride} < widest member "
                             f"({need} tasks)")
        self.pack = int(pack) if pack else self.K
        self.mesh = mesh if mesh is not None else self.members[0].mesh

        # the composite program: K disjoint window slices, pack-wide
        # segments (a solo segment of width 1 has nothing to steal
        # inside a step; the domain segment spans the members)
        seg_d = spec0.segment * self.pack
        self.spec = dataclasses.replace(
            spec0, vocab=spec0.vocab * self.K,
            combine_capacity=spec0.combine_capacity * self.K,
            segment=seg_d, coslots=self.K, costride=self.stride)
        config = dataclasses.replace(cfg0, segment=seg_d)

        # composite address space: member j's bytes at element
        # j * stride * task_size, served through one ordinary TaskPlan
        source = FleetSource([h.feed.source for h in self.members],
                             self.stride * spec0.task_size)
        plan = TaskPlan(n_tasks=self.K * self.stride,
                        task_size=spec0.task_size,
                        n_procs=spec0.n_procs)
        ids, reps = steal.fleet_merge(
            [h.feed.task_ids_grid for h in self.members],
            [h.feed.repeats_grid for h in self.members],
            stride=self.stride, priorities=self.priorities)
        from jax.sharding import NamedSharding, PartitionSpec
        feed = SegmentFeed(
            source, plan, ids, reps, segment=seg_d,
            sharding=NamedSharding(self.mesh, PartitionSpec(AXIS)),
            prefetch=True, budget=feed_budget)
        self.handle = JobHandle(config, self.members[0].backend,
                                self.spec, self.mesh, plan, feed,
                                partitioner=self.members[0].partitioner)
        # members never run engines of their own; their solo feeds stop
        # prefetching now (grids stay readable for result accounting)
        self._member_grids = [
            (np.array(h.feed.task_ids_grid), np.array(h.feed.repeats_grid))
            for h in self.members]
        self._member_n_tasks = [int((g >= 0).sum())
                                for g, _ in self._member_grids]
        for h in self.members:
            h.feed.close()
        self._finalized: set[int] = set()

    # -- introspection -------------------------------------------------------

    @property
    def done(self) -> bool:
        return len(self._finalized) == self.K

    def ready(self) -> bool:
        return self.handle.ready()

    def job_work(self) -> np.ndarray:
        """Executed work per member slot so far — the replicated
        ``carry.job_work`` row (zeros before the first step)."""
        if self.handle._carry is None:
            return np.zeros((self.K,), np.int64)
        return np.asarray(self.handle._carry.job_work)[0].astype(np.int64)

    # -- execution -----------------------------------------------------------

    def step(self, n_segments: int = 1) -> bool:
        """Advance the shared cursor by up to ``n_segments`` domain
        segments (each packs ``pack`` member segments). Returns True
        while map work remains."""
        return self.handle.step(n_segments)

    def collect_finished(self) -> dict[str, JobResult]:
        """Finalize every member whose columns the shared cursor has
        fully consumed (and not finalized yet): one finish-program run
        splits the composite records by key range; each member's
        JobResult is adopted by its handle. Returns {name: result} of
        the newly finished members."""
        consumed = self.handle.feed.consumed_task_ids()
        counts = np.bincount(consumed // self.stride, minlength=self.K) \
            if len(consumed) else np.zeros((self.K,), np.int64)
        newly = [j for j in range(self.K) if j not in self._finalized
                 and counts[j] >= self._member_n_tasks[j]]
        if not newly:
            return {}
        results = self._finalize(newly)
        self._finalized.update(newly)
        return {self.names[j]: results[j] for j in newly}

    def _finalize(self, slots: list[int]) -> dict[int, JobResult]:
        """Run the (pure) finish program on the current carry and split
        its composite records for ``slots``. The carry is NOT mutated —
        the domain keeps scanning; finishing drains a *copy* of the
        in-flight chunk, so a member's last pushed records are covered
        the moment its tasks are all executed."""
        h = self.handle
        assert h._carry is not None, "no carry — domain never stepped"
        _, _, fin_fn = h._seg_fns
        keys, vals, overflow = fin_fn(h._carry)
        keys = np.asarray(keys)[0]
        vals = np.asarray(vals)[0]
        overflow = int(np.asarray(overflow)[0])
        valid = keys != int(KEY_SENTINEL)
        keys, vals = keys[valid], vals[valid]
        base = self.spec.vocab // self.K
        jw = self.job_work()
        total = max(int(jw.sum()), 1)
        out: dict[int, JobResult] = {}
        for j in slots:
            inside = (keys >= j * base) & (keys < (j + 1) * base)
            lk = (keys[inside] - j * base).astype(keys.dtype)
            lv = vals[inside]
            records = Records(lk, lv)
            member = self.members[j]
            gids, greps = self._member_grids[j]
            task_valid = gids >= 0
            out[j] = JobResult(
                records=records,
                output=finalize(member.config.usecase, records),
                keys=lk, values=lv,
                # wall attribution: the domain's engine seconds split by
                # executed work share — the only meaningful per-member
                # cut of a mixed slice
                wall_time=h.wall_time * (int(jw[j]) / total),
                backend=h.backend.name,
                n_tasks=member.plan.n_tasks,
                tasks_per_rank=task_valid.sum(axis=1),
                work_per_rank=(greps * task_valid).sum(axis=1),
                steals_per_rank=np.zeros((self.spec.n_procs,), np.int32),
                partitioner=self.spec.partitioner,
                n_split_keys=0,
                combine_overflow=overflow,
            )
            member.adopt_result(out[j])
        return out

    # -- lifecycle -----------------------------------------------------------

    def close(self):
        """Stop the domain feed's prefetch (member feeds are already
        closed). Idempotent."""
        self.handle.close()

    def checkpoint(self, manager):
        """One snapshot for the whole domain: composite carry + shared
        fleet cursor + merged grids (through the ordinary JobHandle
        path), tagged with the membership so restore can re-form the
        domain before seeking."""
        return self.handle.checkpoint(
            manager, domain_members=list(self.names),
            domain_stride=self.stride, domain_pack=self.pack)

    def restore(self, manager) -> WorkDomain:
        """Resume a mid-co-schedule snapshot: the composite carry is
        installed and the domain feed seeks the shared cursor (saved
        merged grids included) — record-identical to the uninterrupted
        run. Call :meth:`collect_finished` afterwards to re-finalize
        members the saved cursor had already drained."""
        found, extra = manager.peek(None)
        saved = extra.get("domain_members")
        if saved is not None and list(saved) != list(self.names):
            raise ValueError(
                f"domain snapshot at step {found} was taken over members "
                f"{list(saved)} — this domain has {list(self.names)}; "
                "re-form the WorkDomain with the same jobs in the same "
                "order")
        self.handle.restore(manager)
        return self
