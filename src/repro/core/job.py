"""The unified job lifecycle: ``submit(config, dataset) -> JobHandle``.

This is the public entry point of the framework. A job is the triple
(use-case, backend, data source); the handle exposes the paper's
decoupled lifecycle instead of one opaque blocking call:

    cfg = JobConfig(usecase=WordCount(vocab=65_536), backend="1s",
                    task_size=4_096, push_cap=1_024, n_procs=8)
    result = submit(cfg, tokens).result()          # oneshot

    cfg = dataclasses.replace(cfg, segment=2)      # streaming / ckpt mode
    handle = submit(cfg, MmapTokenSource("corpus.bin"))
    while handle.step():                           # one segment at a time
        handle.checkpoint(manager)                 # async window snapshot
    result = handle.result()

``dataset`` is any :class:`repro.data.source.DataSource` (raw arrays are
auto-wrapped in an ``ArraySource``). Nothing is pre-sharded on the host:
a :class:`repro.data.feed.SegmentFeed` reads each segment's tasks by
``plan.file_offset`` in a background thread and dispatches the device
transfer while the engine computes the previous segment — the paper's
non-blocking I/O. Oneshot mode is internally "segmented with one big
segment", so both engines share the one streaming data path. In
segmented mode peak host residency is O(segment); oneshot's single
segment spans the input, so set ``JobConfig(segment=N)`` for datasets
that must never be fully resident.

A checkpoint snapshot carries the feed cursor and task assignment, so
``restore`` *seeks* the stream (no read is replayed), and a straggler
re-plan (``repro.ft.straggler.replan_handle``) re-routes exactly the
not-yet-read tasks through the same feed.

``JobResult`` is structured: the records, the use-case's finalized
output, wall time, and per-rank task/work counts (the imbalance stats the
paper's Fig 4 is about). The records are a read-only ``{key: value}``
mapping over the sorted host key and value arrays
(:class:`repro.core.records.Records`): ``result()`` builds no dict;
``dict(result.records)`` makes a mutable copy.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

from repro.core import coded, obs, planner
from repro.core.kv import KEY_SENTINEL
from repro.core.partition import (Partitioner, resolve_partitioner,
                                  sample_key_histogram)
from repro.core.records import Records
from repro.core.registry import Backend, JobSpec, get_backend
from repro.core.usecase import UseCase, as_map_fn, finalize
from repro.core.windows import AXIS
from repro.data.feed import SegmentFeed
from repro.data.source import as_source
from repro.kernels.backend import on_tpu


@dataclass(frozen=True)
class JobConfig:
    """Declarative job description (replaces ``MapReduceJob.init(...)``)."""
    usecase: UseCase
    backend: str = "1s"
    task_size: int = 4096
    push_cap: int = 1024
    n_procs: int = 8
    segment: int = 0          # 0 -> oneshot; >0 -> tasks per step()
    window: int = 0           # 0 -> usecase.window
    combine_capacity: int = 0
    stealing: bool = False    # device-side work stealing inside the engine
                              #   scan (core/steal.py) — fine-grained
                              #   rebalancing under the host re-planner
    partitioner: str | Partitioner = "hash"
                              # reduce-side key→owner strategy
                              #   (core/partition.py): "hash" (static
                              #   modulo rule), "sampled" (balanced owner
                              #   map from a planner pre-pass),
                              #   "sampled+split" (hot keys spread over
                              #   several owners), or any Partitioner
    fused_map: bool = False   # per-step hot path as one pallas kernel
                              #   (kernels/fused_map) — bit-identical to
                              #   the default unfused path; see the
                              #   README "Fused hot path" section for
                              #   when it wins
    code_rate: int = 1        # coded shuffle (core/coded.py): every map
                              #   task runs on r consecutive ranks and
                              #   the intra-group bucket push becomes
                              #   one XOR-coded multicast block — r×
                              #   map compute for ~1/r shuffle bytes.
                              #   Needs n_procs divisible by r; 1 is
                              #   today's path, bit-identical. See the
                              #   README "Coded shuffle" section.


@dataclass(frozen=True)
class JobResult:
    """Structured outcome of a job."""
    records: Records          # engine output, {key: reduced value} as a
                              #   read-only mapping over the sorted key
                              #   and value arrays (no dict is built;
                              #   dict(records) copies); a two-lane key
                              #   reads (major << 32) | minor
    output: Any               # usecase.finalize(records)
    keys: np.ndarray          # rank-0 sorted keys (sentinel padded); for
                              #   two-lane keys the records' int64 keys
    values: np.ndarray
    wall_time: float          # seconds spent executing (incl. compile)
    backend: str
    n_tasks: int
    tasks_per_rank: np.ndarray   # real (non-padding) tasks *assigned* per rank
    work_per_rank: np.ndarray    # compute-repeats *executed* per rank (with
                                 #   stealing this is the engine's progress
                                 #   row; otherwise it equals the assignment)
    steals_per_rank: np.ndarray  # tasks each rank executed for a peer
                                 #   (all-zero unless stealing was on)
    partitioner: str = "hash"    # reduce-side key→owner strategy that ran
    n_split_keys: int = 0        # hot keys spread over >1 owner (0 unless
                                 #   a splitting partitioner was active)
    combine_overflow: int = 0    # records lost to an undersized
                                 #   combine_capacity anywhere in the
                                 #   Combine phase; result() refuses to
                                 #   hand out records when it is nonzero
                                 #   (CombineOverflowError)
    log_records: int = 0         # records appended to the ranks' logs
                                 #   (two-lane keys; 0 otherwise)

    @property
    def n_steals(self) -> int:
        """Total tasks executed by a rank other than their assignee."""
        return int(self.steals_per_rank.sum())

    @property
    def imbalance(self) -> float:
        """max/mean of per-rank work — 1.0 means perfectly balanced."""
        mean = self.work_per_rank.mean()
        return float(self.work_per_rank.max() / mean) if mean else 1.0


class CombineOverflowError(RuntimeError):
    """The Combine phase lost records to an undersized
    ``combine_capacity`` — the counts in ``self.result.records`` are
    WRONG (previously this truncation was silent). Size
    ``JobConfig(combine_capacity=...)`` to at least the number of
    distinct keys the job produces (0 defaults to the full window,
    which can never overflow)."""

    def __init__(self, result: JobResult):
        self.result = result
        super().__init__(
            f"Combine overflow: {result.combine_overflow} record(s) were "
            f"dropped because combine_capacity is smaller than the number "
            f"of distinct keys — the returned counts would be wrong. "
            f"Raise JobConfig(combine_capacity=...) (>= distinct keys; "
            f"0 uses the full window, which never overflows). The partial "
            f"result is attached as err.result.")


def submit(config: JobConfig, dataset, *, mesh=None, repeats=None,
           prefetch: bool = True, feed_budget=None) -> JobHandle:
    """Plan ``dataset`` (a DataSource, or a 1-D int32 array auto-wrapped
    into one) onto the mesh and return a handle. Nothing executes — and
    nothing beyond one segment is read — until ``step()`` or ``result()``.

    ``repeats`` is the optional (n_procs, tasks_per_proc) compute-repeat
    grid — the paper's footnote-5 imbalance model. ``prefetch=False``
    disables the background read (measurement baselines). ``feed_budget``
    is an optional shared :class:`repro.data.feed.FeedBudget` bounding
    the combined prefetch bytes of many live feeds (the multi-tenant
    scheduler passes its arbiter here)."""
    backend = get_backend(config.backend)        # fail fast on bad names
    partitioner = resolve_partitioner(config.partitioner)  # fail fast too
    key_lanes = getattr(config.usecase, "key_lanes", 1)
    if key_lanes == 2:
        _refuse_two_lane(config, backend, partitioner)
    if config.stealing and not getattr(backend, "supports_stealing", False):
        raise ValueError(
            f"backend {config.backend!r} does not implement device-side "
            "work stealing (no supports_stealing attribute) — drop "
            "stealing=True or use backend '1s'")
    if config.fused_map and not getattr(backend, "supports_fused_map",
                                        False):
        raise ValueError(
            f"backend {config.backend!r} does not implement the fused "
            "map hot path (no supports_fused_map attribute) — drop "
            "fused_map=True or use backend '1s'")
    if config.fused_map and on_tpu():
        raise ValueError(
            "fused_map=True does not run on the TPU: the fused kernel's "
            "in-kernel scatters (kernels/fused_map) do not lower for the "
            "TPU, and its (vocab,) owner maps overflow scalar memory — "
            "drop fused_map; the unfused path computes the same records")
    if config.code_rate > 1 and not getattr(backend, "supports_coded",
                                            False):
        raise ValueError(
            f"backend {config.backend!r} does not implement the coded "
            "exchange (no supports_coded attribute) — drop code_rate or "
            "use backend '1s'")
    source = as_source(dataset)
    plan = planner.plan_input(source.len_elements(), config.task_size,
                              config.n_procs)
    T = plan.tasks_per_proc
    seg_tasks = config.segment if config.segment > 0 else max(T, 1)
    window = config.window or config.usecase.window
    spec = JobSpec(vocab=window, task_size=config.task_size,
                   push_cap=config.push_cap, n_procs=config.n_procs,
                   combine_capacity=config.combine_capacity,
                   segment=config.segment, stealing=config.stealing,
                   fused_map=config.fused_map, code_rate=config.code_rate,
                   partitioner=partitioner.name, key_lanes=key_lanes,
                   # a rank runs every column of the padded segments
                   log_steps=(-(-T // seg_tasks) * seg_tasks
                              if key_lanes == 2 else 0))
    from repro.distributed.mesh import local_mesh
    if mesh is None:
        mesh = local_mesh((config.n_procs,), ("procs",))
    task_ids = planner.shard_task_ids(plan)
    if repeats is None:
        repeats = np.ones((config.n_procs, T), np.int32)
    repeats = np.asarray(repeats, np.int32).reshape(config.n_procs, T)
    if config.code_rate > 1:
        # every member of an r-rank code group carries the group's tasks
        # as r-wide column blocks (core/coded.py); a segment of N blocks
        # is N*r grid columns, so the engine still advances N steps
        task_ids, repeats = coded.replicate_grids(task_ids, repeats,
                                                  config.code_rate)
        seg_tasks *= config.code_rate
    from jax.sharding import NamedSharding, PartitionSpec
    feed = SegmentFeed(
        source, plan, task_ids, repeats, segment=seg_tasks,
        sharding=NamedSharding(mesh, PartitionSpec(AXIS)),
        prefetch=prefetch, budget=feed_budget)
    return JobHandle(config, backend, spec, mesh, plan, feed, partitioner)


def _refuse_two_lane(config: JobConfig, backend, partitioner):
    """Raise ValueError for each setting a two-lane use case cannot run
    under: the log window lives in backend '1s' alone, and the other
    paths assume a one-lane key (a sampled histogram over the window, the
    coded exchange's buckets, the fused kernel's dense window)."""
    name = type(config.usecase).__name__
    if not getattr(backend, "supports_two_lane_keys", False):
        raise ValueError(
            f"{name} emits two-lane keys, which backend "
            f"{config.backend!r} does not carry — use backend '1s'")
    if partitioner.needs_sample:
        raise ValueError(
            f"{name} emits two-lane keys; the {partitioner.name!r} "
            "partitioner samples a one-lane key histogram — use "
            "partitioner='hash'")
    if config.code_rate > 1:
        raise ValueError(
            f"{name} emits two-lane keys, which the coded exchange "
            "(code_rate > 1) does not carry — drop code_rate")
    if config.fused_map:
        raise ValueError(
            f"{name} emits two-lane keys, which the fused map kernel's "
            "dense window cannot hold — drop fused_map")


# the spans whose summed time is ``JobResult.wall_time``: the job's own
# execution, from the partitioner's pre-pass to the records on the host,
# without the records view and ``finalize``
WALL_SPANS = ("mr.partition.sample", "mr.feed.wait", "mr.segment.dispatch",
              "mr.finish", "mr.result.wait", "mr.result.fetch")


class JobHandle:
    """Streaming lifecycle of one submitted job.

    * oneshot (``segment == 0``): ``result()`` streams the whole input as
      one segment through the backend's segmented path and caches the
      outcome;
    * segmented (``segment > 0``): ``step()`` pulls the next prefetched
      segment from the feed and advances the backend's
      ``make_segment_fns`` triple; ``checkpoint(manager)`` snapshots the
      window carry (and feed position) asynchronously; ``restore(manager)``
      resumes by seeking the feed; ``replan(grid)`` re-routes unread
      tasks; ``result()`` finishes the remaining segments and the
      Combine phase.

    ``trace`` holds the job's spans (:mod:`repro.core.obs`): the wait for
    each segment's input, each segment's dispatch, and ``result()`` split
    into finish dispatch, the device's drain, the copies to the host and
    the records view with ``finalize``.
    """

    def __init__(self, config, backend: Backend, spec, mesh, plan,
                 feed: SegmentFeed, partitioner: Partitioner | None = None):
        self.config = config
        self.backend = backend
        self.spec = spec
        self.mesh = mesh
        self.plan = plan
        self.feed = feed
        self.partitioner = (resolve_partitioner(config.partitioner)
                            if partitioner is None else partitioner)
        self._map_fn = as_map_fn(config.usecase)
        self._seg_fns = None
        self._carry = None
        self._owner_ready = False   # sampled owner map installed (or a
                                    #   snapshot's map adopted by restore)
        self.trace = obs.JobTrace()
        self._result: JobResult | None = None

    # -- resource lifecycle -------------------------------------------------

    def close(self):
        """Stop the feed's prefetch thread. Idempotent; safe on a job in
        any state (an abandoned or failed handle must not leak the
        thread)."""
        self.feed.close()

    def __enter__(self) -> JobHandle:
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- introspection ------------------------------------------------------

    @property
    def cursor(self) -> int:
        """Per-rank task slots completed so far (segmented mode)."""
        return self.feed.cursor

    @property
    def done(self) -> bool:
        return self._result is not None

    def ready(self) -> bool:
        """True when the next ``step()`` would not block on input I/O —
        the feed's background read of the upcoming segment has landed
        (or the stream is exhausted / the job is done). The cooperative
        half of the scheduler contract: ``step()`` yields at segment
        boundaries, ``ready()`` lets the scheduler poll many jobs' feeds
        without blocking on any of them."""
        return self._result is not None or self.feed.ready()

    @property
    def carry(self):
        """The current EngineCarry snapshot reference (segmented mode)."""
        return self._carry

    @property
    def _task_ids(self) -> np.ndarray:
        """Full (P, T) task assignment (consumed prefix + upcoming)."""
        return self.feed.task_ids_grid

    @property
    def _repeats(self) -> np.ndarray:
        return self.feed.repeats_grid

    def windows(self) -> np.ndarray:
        """Per-rank dense Key-Value windows, host-side (P, window) — the
        state ``repro.ft.elastic.fold_windows`` redistributes. The 1s
        backend's in-flight ``pending_*`` chunk is folded in so the
        snapshot covers every record of every completed task (exactness
        of a mid-job redistribution depends on it)."""
        assert self._carry is not None, "no carry yet — call step() first"
        self._one_lane("windows()")
        tables = np.array(self._carry.table)                 # copy
        P = tables.shape[0]
        pk = np.asarray(self._carry.pending_k).reshape(P, -1)
        pv = np.asarray(self._carry.pending_v).reshape(P, -1)
        for r in range(P):
            valid = pk[r] != int(KEY_SENTINEL)
            np.add.at(tables[r], pk[r][valid], pv[r][valid])
        return tables

    def _one_lane(self, what: str):
        if self.spec.key_lanes != 1:
            raise ValueError(
                f"{what} works on dense windows; a two-lane job's window "
                "is a log sized to its own task grid — resubmit the job "
                "instead")

    def remaining_task_ids(self) -> np.ndarray:
        """Global ids of tasks not yet executed (segmented mode) — what a
        straggler-aware re-plan redistributes."""
        return self.feed.remaining_task_ids()

    # -- segmented execution ------------------------------------------------

    def _ensure_engine(self):
        if self._seg_fns is None:
            self._seg_fns = self.backend.make_segment_fns(
                self.spec, self._map_fn, self.mesh)
            self._carry = self._seg_fns[0]()

    def _ensure_owner_map(self):
        """Overwrite the carry's hash-seeded owner map with the skew-aware
        one (planner pre-pass through the feed, so the sample bytes land
        in ``feed.stats``). The map is carry *data*: the jitted engine is
        shared across partitioners. Deferred until the first advance /
        checkpoint so a ``restore`` — which adopts the *snapshot's* map
        wholesale — never pays for a sample it would throw away; the
        pre-pass time counts into ``wall_time`` (it is real job cost)."""
        if self._owner_ready:
            return
        self._owner_ready = True
        if not self.partitioner.needs_sample:
            return                      # hash map already seeded by init
        with obs.span("mr.partition.sample", self.trace):
            self._install_partitioner()

    def _install_partitioner(self):
        # sized by the ENGINE's window (spec.vocab — a JobConfig(window=)
        # override may widen it past usecase.window): the owner map must
        # match the compiled carry's shape or restore would reject it
        hist = sample_key_histogram(
            self.feed.sample_tasks, self.plan, self.config.usecase,
            getattr(self.partitioner, "sample_tasks", 16),
            window=self.spec.vocab)
        omap, osplit = self.partitioner.build(hist, self.spec.n_procs)
        P = self.spec.n_procs
        self._carry = self._carry._replace(
            owner_map=np.ascontiguousarray(
                np.broadcast_to(np.asarray(omap, np.int32), (P, len(omap)))),
            owner_split=np.ascontiguousarray(
                np.broadcast_to(np.asarray(osplit, np.int32),
                                (P, len(osplit)))))

    def _ensure_segmented(self):
        if self.config.segment <= 0:
            raise RuntimeError(
                "step()/checkpoint() need a segmented job — set "
                "JobConfig(segment=N) with N tasks per step")
        self._ensure_engine()

    def _advance(self, n_segments: int) -> bool:
        self._ensure_owner_map()
        _, seg_fn, _ = self._seg_fns
        for _ in range(n_segments):
            if self.feed.exhausted:
                break
            with obs.span("mr.feed.wait", self.trace):
                args = (self._carry, *self.feed.next_segment())
            self.trace.note_program("segment", seg_fn, args)
            with obs.span("mr.segment.dispatch", self.trace):
                self._carry = seg_fn(*args)
        return not self.feed.exhausted

    def step(self, n_segments: int = 1) -> bool:
        """Advance up to ``n_segments`` segments. Returns True while map
        work remains (so ``while handle.step(): ...`` drains the job)."""
        if self._result is not None:
            return False
        self._ensure_segmented()
        return self._advance(n_segments)

    def replan(self, task_id_grid) -> JobHandle:
        """Install a re-planned (P, W) assignment of the *unread* tasks
        (from ``repro.ft.straggler``); each task keeps its compute-repeat
        factor, so results stay exact by construction."""
        self._ensure_segmented()
        self._one_lane("replan()")
        if self.spec.code_rate > 1:
            raise ValueError(
                "replan() does not support coded jobs (code_rate > 1): "
                "the r-replicated grid intentionally repeats every task "
                "r times, which the feed's exactly-once coverage "
                "contract rejects; resubmit the job instead")
        grid = np.asarray(task_id_grid, np.int32)
        by_task = {int(t): int(r) for t, r in
                   zip(self.feed.task_ids_grid.ravel(),
                       self.feed.repeats_grid.ravel()) if t >= 0}
        reps = np.ones_like(grid)
        for idx in zip(*np.nonzero(grid >= 0)):
            # unknown ids fall through to the feed's coverage check,
            # which names the offending tasks
            reps[idx] = by_task.get(int(grid[idx]), 1)
        self.feed.replan(grid, reps)
        return self

    def checkpoint(self, manager, **extra):
        """Asynchronously snapshot the window carry into ``manager`` (a
        ``repro.ckpt.CheckpointManager``). The device_get happens in the
        manager's worker thread, overlapping the next segment's compute —
        the paper's MPI-storage-windows trick. The manifest records the
        feed position and task assignment, so restore can seek."""
        self._ensure_segmented()
        self._ensure_owner_map()    # a pre-step snapshot must carry the
        assert self._carry is not None      # sampled map, not the seed
        # reserved keys win over caller extras: restore() trusts them
        return manager.save_async(
            self.cursor, self._carry,
            extra={**extra,
                   "cursor": self.cursor,
                   "backend": self.backend.name,
                   "stealing": self.config.stealing,
                   # cross-job co-scheduling shape: a composite domain
                   # carry cannot restore into a solo handle (or into a
                   # domain of a different width) — the shared fleet
                   # cursor and per-slot work row would be meaningless
                   "coslots": self.spec.coslots,
                   # recorded for provenance only: the fused and unfused
                   # hot paths are bit-identical and share carry shapes,
                   # so snapshots interchange freely across the flag
                   "fused_map": self.spec.fused_map,
                   # the saved grids are r-replicated column blocks for
                   # coded jobs — meaningless under a different r
                   "code_rate": self.spec.code_rate,
                   "partitioner": self.spec.partitioner,
                   "task_ids": self.feed.task_ids_grid.tolist(),
                   "repeats": self.feed.repeats_grid.tolist()})

    def restore(self, manager, step: int | None = None) -> JobHandle:
        """Resume from a snapshot taken by :meth:`checkpoint` (possibly in
        a previous process): install the carry, then *seek* the feed to
        the saved cursor/assignment — no segment read is ever replayed.

        Raises ``ValueError`` if the snapshot was taken by a different
        backend (its carry layout would be silently incompatible)."""
        self._ensure_segmented()
        found, extra = manager.peek(step)
        saved = extra.get("backend")
        if saved is not None and saved != self.backend.name:
            raise ValueError(
                f"checkpoint step {found} "
                f"was taken by backend {saved!r} — it cannot restore into "
                f"a {self.backend.name!r} handle; resubmit with "
                f"JobConfig(backend={saved!r})")
        saved_steal = extra.get("stealing")
        if (saved_steal is not None
                and bool(saved_steal) != self.config.stealing):
            raise ValueError(
                f"checkpoint step {found} was taken with "
                f"stealing={bool(saved_steal)} — restoring into a "
                f"stealing={self.config.stealing} handle would corrupt "
                "the carry's progress/steal accounting; resubmit with "
                f"JobConfig(stealing={bool(saved_steal)})")
        saved_slots = extra.get("coslots")
        if (saved_slots is not None
                and int(saved_slots) != self.spec.coslots):
            raise ValueError(
                f"checkpoint step {found} was taken with "
                f"coslots={int(saved_slots)} — restoring into a "
                f"coslots={self.spec.coslots} handle would misroute the "
                "composite task/key space; re-form the WorkDomain with "
                "the same member jobs first")
        saved_rate = extra.get("code_rate")
        if (saved_rate is not None
                and int(saved_rate) != self.spec.code_rate):
            raise ValueError(
                f"checkpoint step {found} was taken with "
                f"code_rate={int(saved_rate)} — restoring into a "
                f"code_rate={self.spec.code_rate} handle would break the "
                "r-replicated assignment the snapshot's grids encode; "
                f"resubmit with JobConfig(code_rate={int(saved_rate)})")
        saved_part = extra.get("partitioner")
        if saved_part is not None and saved_part != self.spec.partitioner:
            raise ValueError(
                f"checkpoint step {found} was taken with "
                f"partitioner={saved_part!r} — restoring into a "
                f"{self.spec.partitioner!r} handle would mix two owner "
                "maps in one job (the windows already reflect the saved "
                "assignment); resubmit with "
                f"JobConfig(partitioner={saved_part!r})")
        # load exactly the snapshot the guard inspected (a concurrent
        # async save could otherwise re-resolve "latest" to a newer step)
        _, carry, extra = manager.restore(
            jax.eval_shape(lambda: self._carry), step=found)
        self._carry = carry
        self._owner_ready = True    # the snapshot's owner map IS the map
        self.feed.seek(int(extra["cursor"]),
                       task_ids=extra.get("task_ids"),
                       repeats=extra.get("repeats"))
        return self

    def load(self, carry, cursor: int) -> JobHandle:
        """Install an in-memory carry snapshot (elastic/straggler paths).
        The snapshot's owner map comes with it — no re-sample."""
        self._ensure_segmented()
        self._carry = carry
        self._owner_ready = True
        self.feed.seek(int(cursor))
        return self

    def elastic_load(self, table, owner_map, owner_split, task_ids,
                     repeats) -> JobHandle:
        """Resume a job that ran at a *different* process count: install
        windows/owner maps already folded onto this handle's mesh (from
        ``repro.fleet.remesh`` / ``repro.ft.elastic``) plus the
        re-bucketized assignment of the not-yet-executed tasks, and seek
        the feed to column 0 of that new grid.

        Unlike :meth:`load`, the saved carry cannot be adopted wholesale
        — every rank-shaped leaf (``pending_*``, ``work``, ``stolen``)
        has the wrong P. A fresh carry at the new P is semantically
        safe: pending chunks were folded into ``table`` by the caller,
        the steal progress row only seeds future claims, and the cursor
        is monotone bookkeeping. Exactness rests on the Combine dup-sum:
        the folded windows hold every executed record, wherever they
        now live."""
        self._ensure_segmented()
        self._one_lane("elastic_load()")
        P, vocab = self.spec.n_procs, self.spec.vocab
        table = np.ascontiguousarray(np.asarray(table, np.int32))
        if table.shape != (P, vocab):
            raise ValueError(
                f"elastic_load: folded windows have shape {table.shape}, "
                f"this handle runs (n_procs, window) = {(P, vocab)} — "
                "fold onto the NEW mesh before loading")

        def per_rank(m):
            m = np.asarray(m, np.int32)
            if m.ndim == 1:             # replicated row -> per-rank copies
                m = np.broadcast_to(m, (P, len(m)))
            assert m.shape == (P, vocab), m.shape
            return np.ascontiguousarray(m)

        self._carry = self._carry._replace(
            table=table, owner_map=per_rank(owner_map),
            owner_split=per_rank(owner_split))
        self._owner_ready = True        # folded map IS the map: no sample
        self.feed.seek(0, task_ids=task_ids, repeats=repeats)
        return self

    # -- completion ---------------------------------------------------------

    @property
    def wall_time(self) -> float:
        """Seconds this job has spent executing so far: the sum of its
        ``WALL_SPANS``."""
        return self.trace.seconds(*WALL_SPANS)

    def adopt_result(self, result: JobResult) -> JobHandle:
        """Install a result computed on this job's behalf by a
        :class:`~repro.core.workdomain.WorkDomain` (cross-job
        co-scheduling): the member handle never built an engine of its
        own — its tasks ran inside the domain's composite program — but
        the adopted records are exactly the solo outcome (per-job
        dup-sum exactness). The feed stops prefetching; ``result()``
        serves the adopted outcome, overflow check included."""
        assert self._result is None, "job already has a result"
        self._result = result
        self.feed.close()
        return self

    def result(self) -> JobResult:
        """Run to completion (whatever mode) and return the JobResult.
        Oneshot jobs take the same streamed path with one big segment.

        Raises :class:`CombineOverflowError` when the Combine phase lost
        records to an undersized ``combine_capacity`` — the counts would
        be silently wrong otherwise (the partial result rides on the
        error). The feed's prefetch thread is stopped on every exit
        path, success or not — a raising ``segment_fn``/``finish_fn``
        must not leak it."""
        if self._result is None:
            try:
                self._result = self._finish()
            except BaseException:
                self.feed.close()          # error path: don't leak prefetch
                raise
            obs.finished(self.trace)
        if self._result.combine_overflow:
            raise CombineOverflowError(self._result)
        return self._result

    def _finish(self) -> JobResult:
        self._ensure_engine()
        while self._advance(1):
            pass
        self.feed.close()                  # stream drained: stop prefetch
        _, _, fin_fn = self._seg_fns
        self.trace.note_program("finish", fin_fn, (self._carry,))
        with obs.span("mr.finish", self.trace):
            out = fin_fn(self._carry)
        # the device drains the queued segments and runs finish; the
        # copies below then time the transfer alone
        with obs.span("mr.result.wait", self.trace):
            out = jax.block_until_ready(out)
        ids, reps = self.feed.task_ids_grid, self.feed.repeats_grid
        task_valid = ids >= 0
        two_lane = self.spec.key_lanes == 2
        log_records = 0
        with obs.span("mr.result.fetch", self.trace):
            if two_lane:
                (major, minor), vals, overflow, appended = out
                major, minor, vals, overflow = (
                    np.asarray(x)[0] for x in (major, minor, vals, overflow))
                log_records = int(np.asarray(appended).sum())
            else:
                keys, vals, overflow = (np.asarray(x)[0] for x in out)
            split = np.asarray(self._carry.owner_split)[0]
            if self.config.stealing:
                # executed distribution from the engine's psum-maintained
                # progress rows (replicated: every shard holds the same
                # row)
                work = np.asarray(self._carry.work)[0]
                steals = np.asarray(self._carry.stolen)[0]
            else:
                work = (reps * task_valid).sum(axis=1)
                steals = np.zeros((self.config.n_procs,), np.int32)
        with obs.span("mr.result.records", self.trace):
            if two_lane:
                live = major != int(KEY_SENTINEL)
                keys = ((major[live].astype(np.int64) << 32)
                        | minor[live].astype(np.int64))
                vals = vals[live]
                records = Records(keys, vals)
            else:
                valid = keys != int(KEY_SENTINEL)
                records = Records(keys[valid], vals[valid])
            output = finalize(self.config.usecase, records)
        return JobResult(
            records=records,
            output=output,
            keys=keys, values=vals,
            wall_time=self.wall_time,
            backend=self.backend.name,
            n_tasks=self.plan.n_tasks,
            tasks_per_rank=task_valid.sum(axis=1),
            work_per_rank=work,
            steals_per_rank=steals,
            partitioner=self.spec.partitioner,
            n_split_keys=int((split > 1).sum()),
            combine_overflow=int(overflow),   # psum-replicated
            log_records=log_records,
        )
