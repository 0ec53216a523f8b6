"""SegmentFeed — the paper's non-blocking I/O, feeding the engines.

"Each process asynchronously retrieves the input for the next Map task
while computing the current one" (§2.1): a background thread reads
segment t+1's tasks from a :class:`~repro.data.source.DataSource` by
``plan.file_offset`` and dispatches the host→device transfer
(``jax.device_put`` is async) while the device executes segment t —
generalizing :class:`repro.data.pipeline.DoubleBufferedLoader` from LM
batches to engine segments.

The feed owns the *assignment state* of a streaming job: the per-rank
task-id / compute-repeat grids and the column cursor. That makes it the
natural seam for

  * checkpoint restore — ``seek(cursor, ...)`` repositions the stream
    without replaying any read;
  * straggler mitigation — ``replan(...)`` swaps the not-yet-read
    columns for a throughput-proportional reassignment (the unread
    tasks are re-routed; reads are pure, so a discarded prefetch is
    just dropped).

Segments are padded to a fixed ``segment`` column width with no-op
tasks (id -1, all-sentinel tokens), so every call of the engines'
``segment_fn`` shares one compiled program regardless of tail segments
or re-planned widths.

Peak host residency is O(segment): the feed holds at most the segment
being consumed plus the one in flight (``stats.max_live_bytes`` is the
evidence the memory-bound tests pin).

With many jobs live at once (``repro.core.scheduler.JobScheduler``),
N feeds prefetch concurrently; a shared :class:`FeedBudget` arbiter
bounds their *combined* in-flight bytes so tenant prefetch cannot OOM
the host. A denied reservation only skips the background read — the
segment is built synchronously at consume time instead — so the budget
can never deadlock a job, it only serializes its I/O.
"""
from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np


@dataclass
class FeedStats:
    """Observability counters (host side, not device memory)."""
    bytes_read: int = 0          # total bytes materialized from the source
    segments_built: int = 0
    prefetch_hits: int = 0       # segments served from the background read
    prefetch_misses: int = 0     # segments built synchronously
    max_live_bytes: int = 0      # high-water mark of feed-held host bytes
    sample_tasks_read: int = 0   # tasks read by a partitioner pre-pass
                                 #   (core/partition.py) — their bytes are
                                 #   included in bytes_read
    budget_denials: int = 0      # prefetches skipped because the shared
                                 #   FeedBudget was exhausted (the segment
                                 #   was built synchronously instead)
    _live: dict = field(default_factory=dict, repr=False)

    def _track(self, key, nbytes: int):
        self._live[key] = nbytes
        self.max_live_bytes = max(self.max_live_bytes,
                                  sum(self._live.values()))

    def _release(self, key):
        self._live.pop(key, None)


class FeedBudget:
    """Shared in-flight-bytes arbiter across many live SegmentFeeds.

    One scheduler-owned instance is passed to every feed it creates
    (``submit(..., feed_budget=...)``); a feed must reserve the estimated
    segment bytes before scheduling a *background* read. When the
    combined reservations would exceed ``max_live_bytes`` the prefetch is
    denied (counted in the feed's ``stats.budget_denials``) and the
    segment is built synchronously at consume time — bounded host
    memory, never a stalled job.

    One reservation is always granted when nothing is held, so a single
    oversized segment degrades to serialized prefetch instead of
    disabling prefetch fleet-wide.
    """

    def __init__(self, max_live_bytes: int):
        assert max_live_bytes > 0, "budget must be positive bytes"
        self.max_live_bytes = int(max_live_bytes)
        self._held: dict = {}
        self._lock = threading.Lock()
        self.denials = 0             # fleet-wide (per-feed copies in stats)

    @property
    def live_bytes(self) -> int:
        with self._lock:
            return sum(self._held.values())

    def try_reserve(self, key, nbytes: int) -> bool:
        with self._lock:
            if (self._held
                    and sum(self._held.values()) + nbytes
                    > self.max_live_bytes):
                self.denials += 1
                return False
            self._held[key] = int(nbytes)
            return True

    def release(self, key):
        with self._lock:
            self._held.pop(key, None)


class SegmentFeed:
    """Pull-based segment stream over a DataSource for one job.

    ``next_segment()`` returns ``(tokens, task_ids, repeats)`` host/device
    blocks of shape ``(P, segment, S)`` / ``(P, segment)`` and schedules
    the following segment's read+transfer in the background.
    """

    def __init__(self, source, plan, task_ids: np.ndarray,
                 repeats: np.ndarray, segment: int,
                 *, sharding=None, prefetch: bool = True,
                 budget: FeedBudget | None = None):
        self.source = source
        self.plan = plan
        self.segment = int(segment)
        assert self.segment > 0, "segment width must be positive"
        self._ids = np.array(task_ids, np.int32)       # (P, T)
        self._reps = np.array(repeats, np.int32)       # (P, T)
        self._cursor = 0                               # columns consumed
        self._sharding = sharding
        self._prefetch = prefetch
        self._budget = budget
        self._budget_key = None                        # held reservation
        self._gen = 0                                  # seek/replan epoch
        self._pending: tuple[int, int, Future] | None = None
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="segment-feed")
        self._closed = False
        self._lock = threading.Lock()
        self._stats_lock = threading.Lock()   # feed thread vs seek/replan
        self.stats = FeedStats()

    # -- assignment state ---------------------------------------------------

    @property
    def cursor(self) -> int:
        return self._cursor

    @property
    def total_columns(self) -> int:
        return self._ids.shape[1]

    @property
    def task_ids_grid(self) -> np.ndarray:
        """The full (P, T) assignment, consumed prefix included."""
        return self._ids

    @property
    def repeats_grid(self) -> np.ndarray:
        return self._reps

    @property
    def exhausted(self) -> bool:
        return self._cursor >= self.total_columns

    def remaining_task_ids(self) -> np.ndarray:
        """Global ids of the not-yet-consumed tasks, sorted."""
        ids = self._ids[:, self._cursor:]
        return np.sort(ids[ids >= 0])

    def consumed_task_ids(self) -> np.ndarray:
        """Global ids of the already-executed tasks (columns before the
        cursor), sorted. Over a composite fleet grid these are (job,
        task) ids — how a :class:`~repro.core.workdomain.WorkDomain`
        detects that one member job fully drained mid-co-schedule and
        can be finalized while its siblings keep running."""
        ids = self._ids[:, : self._cursor]
        return np.sort(ids[ids >= 0])

    def read_tasks(self, task_ids) -> np.ndarray:
        """Serve arbitrary tasks by *global id*, independent of the
        assignment grids or cursor — the host-side twin of the engine's
        steal fetch. Over a :class:`~repro.data.source.FleetSource` the
        global id is a composite (job, task) id, so one feed serves task
        reads across job boundaries — the cross-job steal fetch and a
        domain checkpoint restore address members through this same
        path. Reads are pure, so serving a task to a rank other
        than its original assignee replays nothing and disturbs no
        stream position; the bytes still count into ``stats``."""
        from repro.core.planner import read_tasks
        tokens = read_tasks(self.source, self.plan, task_ids)
        with self._stats_lock:
            self.stats.bytes_read += tokens.nbytes
        return tokens

    def sample_tasks(self, task_ids) -> np.ndarray:
        """:meth:`read_tasks` for a partitioner's sampling pre-pass —
        same pure by-global-id read, separately accounted so a job's
        stats show what the skew sample cost."""
        tokens = self.read_tasks(task_ids)
        with self._stats_lock:
            self.stats.sample_tasks_read += int(np.asarray(task_ids).size)
        return tokens

    # -- segment construction ----------------------------------------------

    def _build(self, start: int, gen: int):
        """Read one segment's tasks by file offset and dispatch the
        device transfer — the body that runs in the feed thread. Under a
        profiler it shows as the span ``mr.feed.build``."""
        from repro.core import obs                      # lazy: no cycle
        with obs.span("mr.feed.build"):
            end = min(start + self.segment, self.total_columns)
            P = self._ids.shape[0]
            ids = np.full((P, self.segment), -1, np.int32)
            reps = np.ones((P, self.segment), np.int32)
            ids[:, : end - start] = self._ids[:, start:end]
            reps[:, : end - start] = self._reps[:, start:end]
            from repro.core.planner import gather_segment  # lazy: no cycle
            tokens = gather_segment(self.source, self.plan, ids)
            with self._stats_lock:
                self.stats.bytes_read += tokens.nbytes
                self.stats.segments_built += 1
                if gen == self._gen:    # a stale prefetch after
                    # seek/replan is not tracked: it would leak
                    self.stats._track((gen, start), tokens.nbytes)
            if self._sharding is not None:
                import jax
                tokens = jax.device_put(tokens, self._sharding)  # async
            return tokens, ids, reps

    def _schedule(self, start: int):
        if (self._closed or not self._prefetch
                or start >= self.total_columns):
            self._pending = None
            return
        gen = self._gen
        if self._budget is not None:
            # reserve the estimated segment bytes before the background
            # read; a denial is not an error — next_segment just builds
            # the segment synchronously when it gets there
            est = (self._ids.shape[0] * self.segment
                   * self.plan.task_size * 4)
            key = (id(self), gen, start)
            if not self._budget.try_reserve(key, est):
                with self._stats_lock:
                    self.stats.budget_denials += 1
                self._pending = None
                return
            self._budget_key = key
        self._pending = (gen, start,
                         self._pool.submit(self._build, start, gen))

    def _drop_budget(self):
        if self._budget is not None and self._budget_key is not None:
            self._budget.release(self._budget_key)
            self._budget_key = None

    # -- the streaming contract --------------------------------------------

    def next_segment(self):
        """Return the next ``(tokens, task_ids, repeats)`` segment and
        kick off the background read of the one after; ``None`` when the
        stream is exhausted."""
        with self._lock:
            if self.exhausted:
                return None
            start, gen = self._cursor, self._gen
            if (self._pending is not None
                    and self._pending[:2] == (gen, start)):
                seg = self._pending[2].result()
                self.stats.prefetch_hits += 1
            else:
                seg = self._build(start, gen)
                self.stats.prefetch_misses += 1
            with self._stats_lock:
                self.stats._release((gen, start))
            self._drop_budget()
            self._cursor = min(start + self.segment, self.total_columns)
            self._schedule(self._cursor)
            return seg

    def ready(self) -> bool:
        """True when :meth:`next_segment` would not block on input I/O:
        the stream is exhausted (returns None immediately), or the
        background read of the segment at the cursor has completed. A
        scheduler polls this to time-slice the job whose data is already
        on its way to the device (``JobHandle.ready``)."""
        with self._lock:
            if self.exhausted or self._closed:
                return True
            p = self._pending
            return (p is not None and p[:2] == (self._gen, self._cursor)
                    and p[2].done())

    def prime(self):
        """Kick off the background read of the segment at the cursor
        without consuming anything — so a freshly admitted job's first
        segment prefetches while *other* jobs run their slices.
        Idempotent; a no-op when a prefetch is already pending (or the
        shared budget denies the reservation)."""
        with self._lock:
            if self._pending is None:
                self._schedule(self._cursor)

    def seek(self, cursor: int, task_ids=None, repeats=None):
        """Reposition the stream (checkpoint restore): install the saved
        assignment grids and cursor. No segment before ``cursor`` is ever
        re-read — restore seeks, it does not replay."""
        with self._lock:
            if task_ids is not None:
                self._ids = np.array(task_ids, np.int32)
            if repeats is not None:
                self._reps = np.array(repeats, np.int32)
            self._cursor = int(cursor)
            self._invalidate()
        return self

    def replan(self, task_ids: np.ndarray, repeats: np.ndarray):
        """Re-route the *unread* tasks (straggler mitigation): columns
        before the cursor keep their history; columns from the cursor on
        are replaced by the new (P, W) assignment. Any in-flight prefetch
        of the old assignment is discarded."""
        task_ids = np.asarray(task_ids, np.int32)
        repeats = np.asarray(repeats, np.int32)
        assert task_ids.shape == repeats.shape
        assert task_ids.shape[0] == self._ids.shape[0], "rank count fixed"
        with self._lock:
            done = self._ids[:, : self._cursor]
            old = set(self.remaining_task_ids().tolist())
            new = task_ids[task_ids >= 0].tolist()
            assert sorted(new) == sorted(old), (
                "replan must cover exactly the unread tasks once "
                f"(unread={sorted(old)}, got={sorted(new)})")
            self._ids = np.concatenate([done, task_ids], axis=1)
            self._reps = np.concatenate(
                [self._reps[:, : self._cursor], repeats], axis=1)
            self._invalidate()
        return self

    def _invalidate(self):
        with self._stats_lock:
            self._gen += 1
            self.stats._live.clear()
        if self._pending is not None:
            self._pending[2].cancel()
            self._pending = None
        self._drop_budget()
        self._schedule(self._cursor)

    def close(self):
        """Stop the prefetch thread. Idempotent; a closed feed can still
        be consumed (reads fall back to the caller's thread)."""
        with self._lock:
            if not self._closed:
                self._closed = True
                self._pending = None
                self._drop_budget()
                self._pool.shutdown(wait=False)
