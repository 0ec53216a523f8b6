"""MapReduce-1S — the paper's decoupled one-sided engine, TPU-native.

Structure (paper §2.1, Fig 1) and its JAX mapping:

  Map + Local Reduce   scan step t: map_fn -> owner lookup ->
                       reduce_and_bucketize (one keyed sort)
  one-sided put        per-step small all_to_all pushes task t's buckets
                       into every owner's Key-Value window; XLA's async
                       collectives let the push of step t overlap the map of
                       step t+1 (the carry holds the in-flight chunk, folded
                       one step later — an explicit double buffer)
  Reduce               incremental: each received chunk is folded into the
                       dense Key-Value window immediately (no post-barrier
                       reduce spike — this is where the imbalance win lives)
  ownership transfer   bucket overflow stays local and is folded into the
                       mapper's own window (paper footnote 2); the Combine
                       dup-sum makes the result exact
  Combine              ⌈log2 P⌉-level merge tree (core/combine.py)

Registered as backend ``"1s"`` (:mod:`repro.core.registry`). Both the
blocking ``run_job`` and the segmented ``make_segment_fns`` paths are
methods of :class:`OneSidedBackend`, sharing the per-step body — the
segmented path is what the checkpoint layer snapshots between calls (the
paper's "window sync after each Map task" storage-window checkpoints).
"""
from __future__ import annotations

from functools import partial
from collections.abc import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from repro.core.combine import tree_combine
from repro.core.kv import (KEY_SENTINEL, local_reduce_repeated,
                           reduce_and_bucketize, reduce_runs)
from repro.core.partition import lookup_owner
from repro.core.registry import JobSpec, memoized, register_backend
from repro.core.windows import (AXIS, DenseWindow, EngineCarry,
                                STATUS_REDUCE, combine_records, compact_log,
                                init_carry, log_width, wrap_segment_fns)
from repro.distributed.collectives import (all_to_all_blocks, coded_exchange,
                                           match_vma)
from repro.kernels.fused_map.ops import fused_map_step


def _step(spec: JobSpec, map_fn: Callable, carry: EngineCarry, xs):
    if spec.key_lanes == 2:
        return _log_step(spec, map_fn, carry, xs)
    task, task_id, rep = xs
    P, cap = spec.n_procs, spec.push_cap
    with jax.named_scope("map"):
        if spec.coslots > 1:
            # cross-job co-scheduling (core/workdomain.py): the composite
            # task id encodes (member job slot, local task id). The
            # map_fn must see the LOCAL id (use-cases key records by task
            # id), and every emitted key is offset into the owning job's
            # disjoint window slice — per-job dup-sum exactness then
            # follows from the solo argument, window by window. Executed
            # repeats land in the psum-maintained per-slot row so the
            # scheduler charges tenants for work actually run, wherever
            # stealing routed it.
            base = spec.vocab // spec.coslots
            live = task_id >= 0
            slot = jnp.where(live, task_id // spec.costride, 0)
            local_id = jnp.where(live, task_id - slot * spec.costride,
                                 task_id)
            keys, vals = map_fn(task, local_id, rep)
            keys = jnp.where(keys == KEY_SENTINEL, keys,
                             keys + slot * base)
            carry = carry._replace(job_work=carry.job_work + lax.psum(
                jnp.zeros((spec.coslots,), jnp.int32).at[slot].add(
                    jnp.where(live, rep, 0)), AXIS))
        else:
            # Phase I: Map (+ simulated imbalance via data-dependent
            # repeats)
            keys, vals = map_fn(task, task_id, rep)
    if spec.fused_map:
        # Phases II+III fused into one pallas kernel (kernels/fused_map):
        # local reduce, owner lookup, bucketize and both window folds in
        # a single vocab pass — bit-identical to the unfused path below.
        with jax.named_scope("fused_map"):
            table, bk, bv, counts = fused_map_step(
                keys, vals, rep, task_id, carry.owner_map,
                carry.owner_split, carry.pending_k, carry.pending_v,
                carry.table, n_procs=P, cap=cap)
        with jax.named_scope("push"):
            rk = all_to_all_blocks(bk, AXIS)
            rv = all_to_all_blocks(bv, AXIS)
        return carry._replace(table=table, pending_k=rk, pending_v=rv,
                              cursor=carry.cursor + 1), counts
    # one-sided put: bucket by the carried owner map (hash rule by
    # default; a skew-aware map from core/partition.py otherwise), looked
    # up on the raw records so that one sort serves Phase II (Local
    # Reduce, inside Map, as in the paper) and the bucketing. The repeat
    # factor re-computes the whole task (paper footnote 5) — per-rank
    # while-trip-counts differ, which is exactly the imbalance mechanism.
    with jax.named_scope("route"):
        owners = lookup_owner(carry.owner_map, carry.owner_split, keys,
                              task_id, P)
    bk, bv, counts, (ofk, ofv) = reduce_and_bucketize(
        keys, vals, owners, P, cap, rep)
    with jax.named_scope("push"):
        rk = all_to_all_blocks(bk, AXIS)
        rv = all_to_all_blocks(bv, AXIS)
    # Phase III (incremental Reduce): fold the *previous* step's chunk while
    # this step's push is still in flight (double buffer).
    with jax.named_scope("fold"):
        win = DenseWindow(carry.table).put(carry.pending_k.reshape(-1),
                                          carry.pending_v.reshape(-1))
        # ownership transfer for overflowed records: keep them locally
        win = win.put(ofk, ofv)
    return carry._replace(table=win.table, pending_k=rk, pending_v=rv,
                          cursor=carry.cursor + 1), counts


def _log_step(spec: JobSpec, map_fn: Callable, carry: EngineCarry, xs):
    """One step for two-lane keys: the records go to the rank's
    :class:`~repro.core.windows.LogWindow` instead of a dense window.

    Map emits ``((major, minor), values)``; the owner of a record is the
    owner map's entry for its major lane, so all of a major key's records
    meet on one owner. Local Reduce sorts on (owner, major, minor). At
    P=1 the task's reduced records are appended to the log as they are;
    at P>1 the buckets carry both lanes through the push, and each step
    appends the chunk received one step earlier and the overflow that
    stays local. Step ``cursor`` writes the log's ``cursor``-th region,
    so nothing is merged until the finish compacts the log.
    """
    task, task_id, rep = xs
    P, cap = spec.n_procs, spec.push_cap
    with jax.named_scope("map"):
        keys, vals = map_fn(task, task_id, rep)
    with jax.named_scope("route"):
        owners = lookup_owner(carry.owner_map, carry.owner_split, keys[0],
                              task_id, P)
    at = carry.cursor * log_width(spec)
    if P == 1:
        so, sk, _, last, sums = reduce_runs(keys, vals, owners, P, rep)
        with jax.named_scope("log_append"):
            tail = last & (so < P)
            log = carry.table.append(
                at, *(jnp.where(tail, k, KEY_SENTINEL) for k in sk),
                jnp.where(tail, sums, 0))
        return carry._replace(table=log, cursor=carry.cursor + 1), None
    bk, bv, counts, (ofk, ofv) = reduce_and_bucketize(keys, vals, owners,
                                                      P, cap, rep)
    with jax.named_scope("push"):
        rk = tuple(all_to_all_blocks(k, AXIS) for k in bk)
        rv = all_to_all_blocks(bv, AXIS)
    with jax.named_scope("log_append"):
        log = carry.table.append(
            at, *(jnp.concatenate([p.reshape(-1), o])
                  for p, o in zip(carry.pending_k, ofk)),
            jnp.concatenate([carry.pending_v.reshape(-1), ofv]))
    return carry._replace(table=log, pending_k=rk, pending_v=rv,
                          cursor=carry.cursor + 1), counts


def _coded_step(spec: JobSpec, map_fn: Callable, carry: EngineCarry, xs):
    """One step of the r-replicated coded engine (``code_rate`` r > 1).

    The scan consumes one r-wide COLUMN BLOCK per step: every member of
    an r-rank code group holds the identical block (the group's members'
    r=1 tasks at this column, ``core/coded.py``), maps all r tasks (the
    r× compute the coded trade pays), unions the emissions under the
    local-reduce dup-sum, and replaces the r-1 intra-group unicast
    bucket rows with ONE XOR-coded multicast block
    (``distributed/collectives.coded_exchange``). Exactness: each
    record folds exactly once fleet-wide — one speaker per inter-group
    destination, one designated-peer decode per intra-group destination,
    one rotating member retaining the bucket overflow — and the Combine
    dup-sum makes the result independent of where records fold, the
    same argument that covers stealing at r=1.
    """
    task, task_id, rep = xs            # (r, S), (r,), (r,)
    P, cap, r = spec.n_procs, spec.push_cap, spec.code_rate
    me = lax.axis_index(AXIS)
    # Phases I+II per replica task, then union under the dup-sum
    ks, vs = [], []
    for j in range(r):
        with jax.named_scope("map"):
            keys, vals = map_fn(task[j], task_id[j], rep[j])
        with jax.named_scope("local_reduce"):
            uk, uv = local_reduce_repeated(keys, vals, keys.shape[0],
                                           rep[j])
        ks.append(uk)
        vs.append(uv)
    uk, uv = jnp.concatenate(ks), jnp.concatenate(vs)
    # the block's first id picks split replicas for the whole union: any
    # group-replicated choice is exact (dup-sum locality independence)
    with jax.named_scope("route"):
        owners = lookup_owner(carry.owner_map, carry.owner_split, uk,
                              task_id[0], P)
    bk, bv, counts, (ofk, ofv) = reduce_and_bucketize(uk, uv, owners, P,
                                                      cap)
    with jax.named_scope("push"):
        rk, rv = coded_exchange(bk, bv, AXIS, r)
    with jax.named_scope("fold"):
        win = DenseWindow(carry.table).put(carry.pending_k.reshape(-1),
                                          carry.pending_v.reshape(-1))
        # overflow: all members hold the identical union overflow —
        # exactly one (cursor-rotating) member of each group folds it
        keep = (carry.cursor % r) == (me % r)
        win = win.put(jnp.where(keep, ofk, KEY_SENTINEL),
                      jnp.where(keep, ofv, 0))
    return carry._replace(table=win.table, pending_k=rk, pending_v=rv,
                          cursor=carry.cursor + 1), counts


def _drain(carry: EngineCarry) -> EngineCarry:
    """Fold the last in-flight chunk; enter STATUS_REDUCE -> done."""
    win = DenseWindow(carry.table).put(carry.pending_k.reshape(-1),
                                      carry.pending_v.reshape(-1))
    P, cap = carry.pending_k.shape
    return carry._replace(
        table=win.table,
        pending_k=jnp.full((P, cap), KEY_SENTINEL, jnp.int32),
        pending_v=jnp.zeros((P, cap), jnp.int32),
        status=jnp.int32(STATUS_REDUCE),
    )


def _steal_segment(spec: JobSpec, map_fn: Callable, carry: EngineCarry,
                   tok, tid, rep) -> EngineCarry:
    """Advance one segment with device-side work stealing (core/steal.py).

    Per scan step: (1) every rank runs the pure claim function over the
    shared cursor state, so all ranks agree on who executes which task
    slot; (2) each claimed task is *fetched by global task id* from the
    rank that holds its input — a fixed-shape ``[tokens | id | repeat]``
    all_to_all, the one-sided "get" mirroring the push shuffle; (3) the
    executed repeat lands in the carry's psum-maintained progress row,
    which is exactly the state the next step's claims read.
    """
    from repro.core import steal
    P, S = spec.n_procs, spec.task_size
    me = lax.axis_index(AXIS)
    # deques address dense [0, count) ranges: real columns first
    perm = steal.compact_columns(tid)
    tok, tid, rep = tok[perm], tid[perm], rep[perm]
    # the replicated cursors enter the scan with the progress row's type
    head, tail = match_vma(steal.segment_cursors(tid, AXIS), carry.work)
    onehot = jnp.arange(P) == me

    def step(state, _):
        carry, head, tail = state
        with jax.named_scope("claim"):
            src_rank, src_col, head, tail = steal.claim_step(head, tail,
                                                             carry.work)
        # serve: the rank owning each claimed slot ships that task's
        # input + (global id, repeat) to its executor
        with jax.named_scope("fetch"):
            mine = src_rank == me
            cols = jnp.where(mine, src_col, 0)
            served = jnp.concatenate(
                [jnp.where(mine[:, None], tok[cols], KEY_SENTINEL),
                 jnp.where(mine[:, None],
                           jnp.stack([tid[cols], rep[cols]], axis=1),
                           jnp.asarray([-1, 0], jnp.int32))], axis=1)
            got = all_to_all_blocks(served, AXIS)
            src = src_rank[me]
            row = got[jnp.maximum(src, 0)]
            live = src >= 0
            task = jnp.where(live, row[:S], KEY_SENTINEL)
            t_id = jnp.where(live, row[S], -1)
            t_rep = jnp.where(live, row[S + 1], 0)
        with jax.named_scope("claim"):
            carry = carry._replace(
                work=carry.work + lax.psum(
                    jnp.where(onehot & live, t_rep, 0), AXIS),
                stolen=carry.stolen + lax.psum(
                    jnp.where(onehot & live & (src != me), 1, 0), AXIS))
        carry, _ = _step(spec, map_fn, carry,
                         (task, t_id, jnp.maximum(t_rep, 1)))
        return (carry, head, tail), None

    (carry, _, _), _ = lax.scan(step, (carry, head, tail), None,
                                length=tok.shape[0])
    return carry


def _coded_steal_segment(spec: JobSpec, map_fn: Callable,
                         carry: EngineCarry, tok, tid, rep) -> EngineCarry:
    """Work stealing over r-replicated grids: claims move whole r-wide
    column blocks between GROUPS (G = P/r super-ranks of the same pure
    claim function), so a stolen block lands on all r members of the
    claimant group and its code group stays decodable. Member m of the
    victim group serves member m of each claimant group the full
    ``(r, S+2)`` block through the same fixed-shape all_to_all get as
    the r=1 steal path.
    """
    from repro.core import steal
    P, S, r = spec.n_procs, spec.task_size, spec.code_rate
    G = P // r
    me = lax.axis_index(AXIS)
    g, m = me // r, me % r
    # block-granular views of the segment: (W, S) -> (W//r, r, S)
    n_blk = tok.shape[0] // r
    tok = tok.reshape(n_blk, r, S)
    tid = tid.reshape(n_blk, r)
    rep = rep.reshape(n_blk, r)
    # real blocks first (any live sub-task keeps a block claimable)
    blk_valid = (tid >= 0).any(axis=1)
    perm = jnp.argsort(~blk_valid)
    tok, tid, rep = tok[perm], tid[perm], rep[perm]
    # group deques: every member holds the identical grid row, so the
    # one-hot psum over groups counts each block r times — divide out
    count = blk_valid.sum().astype(jnp.int32)
    tail = lax.psum(jnp.where(jnp.arange(G) == g, count, 0), AXIS) // r
    head, tail = match_vma((jnp.zeros_like(tail), tail), carry.work)
    onehot = jnp.arange(P) == me
    e_grp = jnp.arange(P) // r
    e_mem = jnp.arange(P) % r

    def step(state, _):
        carry, head, tail = state
        with jax.named_scope("claim"):
            # per-group work row: members of a group accrue identically
            gwork = carry.work.reshape(G, r)[:, 0]
            src_grp, src_col, head, tail = steal.claim_step(head, tail,
                                                            gwork)
        with jax.named_scope("fetch"):
            mine = (src_grp[e_grp] == g) & (e_mem == m)
            cols = jnp.where(mine, src_col[e_grp], 0)
            served = jnp.concatenate(
                [jnp.where(mine[:, None], tok[cols].reshape(P, r * S),
                           KEY_SENTINEL),
                 jnp.where(mine[:, None], tid[cols], -1),
                 jnp.where(mine[:, None], rep[cols], 0)], axis=1)
            got = all_to_all_blocks(served, AXIS)
            src = src_grp[g]
            row = got[jnp.maximum(src * r + m, 0)]
            live = src >= 0
            task = jnp.where(live, row[:r * S],
                             KEY_SENTINEL).reshape(r, S)
            t_id = jnp.where(live, row[r * S:r * S + r], -1)
            t_rep = jnp.where(live, row[r * S + r:], 0)
        with jax.named_scope("claim"):
            done = jnp.where(t_id >= 0, t_rep, 0).sum()
            carry = carry._replace(
                work=carry.work + lax.psum(
                    jnp.where(onehot & live, done, 0), AXIS),
                stolen=carry.stolen + lax.psum(
                    jnp.where(onehot & live & (src != g), 1, 0), AXIS))
        carry, _ = _coded_step(spec, map_fn, carry,
                               (task, t_id, jnp.maximum(t_rep, 1)))
        return (carry, head, tail), None

    (carry, _, _), _ = lax.scan(step, (carry, head, tail), None,
                                length=n_blk)
    return carry


def _shard_spec():
    from jax.sharding import PartitionSpec as P
    return P(AXIS)


def _engine(spec: JobSpec, map_fn: Callable, tokens, task_ids, repeats):
    """Per-shard engine body. tokens: (1, T, S); task_ids/repeats: (1, T)."""
    tokens, task_ids, repeats = tokens[0], task_ids[0], repeats[0]
    carry = init_carry(spec)
    if spec.code_rate > 1:
        if spec.stealing:
            carry = _coded_steal_segment(spec, map_fn, carry, tokens,
                                         task_ids, repeats)
        else:
            r = spec.code_rate
            nb = task_ids.shape[0] // r
            carry, _ = lax.scan(
                partial(_coded_step, spec, map_fn), carry,
                (tokens.reshape(nb, r, -1), task_ids.reshape(nb, r),
                 repeats.reshape(nb, r)))
    elif spec.stealing:
        carry = _steal_segment(spec, map_fn, carry, tokens, task_ids,
                               repeats)
    else:
        carry, _ = lax.scan(partial(_step, spec, map_fn), carry,
                            (tokens, task_ids, repeats))
    carry = _drain(carry)
    # Combine (phase IV): sorted merge tree (run_job is the legacy
    # blocking path — the Job API's segmented fin surfaces the overflow
    # count; here an undersized combine_capacity still truncates)
    keys, vals, overflow = combine_records(carry.table, spec)
    keys, vals, _ = tree_combine(keys, vals, AXIS, spec.n_procs, overflow)
    return keys[None], vals[None]


@register_backend("1s")
class OneSidedBackend:
    """The decoupled engine behind the ``Backend`` protocol."""

    # the engine honors JobSpec.stealing (device-side work stealing,
    # core/steal.py); submit() refuses the flag on backends without this
    supports_stealing = True
    # ... and JobSpec.fused_map (the pallas-fused per-step hot path,
    # kernels/fused_map), gated by submit() the same way
    supports_fused_map = True
    # ... and JobSpec.coslots > 1 (cross-job co-scheduling — one engine
    # program executing a composite task/key space merged from several
    # program-compatible jobs, core/workdomain.py). The scheduler only
    # forms WorkDomains over backends advertising this.
    supports_coschedule = True
    # ... and JobSpec.code_rate > 1 (the r-replicated coded shuffle:
    # core/coded.py grids + the XOR multicast exchange), gated by
    # submit() like the other capability flags
    supports_coded = True
    # ... and JobSpec.key_lanes == 2 (two-lane keys into a LogWindow)
    supports_two_lane_keys = True

    def __init__(self):
        self._programs: dict = {}

    def run_job(self, spec: JobSpec, map_fn: Callable, mesh, tokens,
                task_ids, repeats):
        """Full job. tokens: (P, T, S) host array. Returns rank-0
        records."""
        if spec.key_lanes != 1:
            raise ValueError("run_job carries one-lane keys only; run "
                             "two-lane jobs through submit()")
        P = _shard_spec()
        fn = memoized(
            self._programs, ("run", spec, map_fn, mesh),
            lambda: jax.jit(shard_map(
                partial(_engine, spec, map_fn), mesh=mesh,
                in_specs=(P, P, P), out_specs=(P, P),
                check_vma=not spec.fused_map)))
        keys, vals = fn(tokens, task_ids, repeats)
        return jax.device_get(keys)[0], jax.device_get(vals)[0]

    def trace_handles(self, spec: JobSpec, map_fn: Callable, mesh,
                      seg_tasks: int = 2, tag: str = ""):
        """Traceable :class:`~repro.core.registry.ProgramHandle`\\ s for
        fleetlint (repro.analysis) — the segmented triple plus the
        replication contract the steal protocol relies on."""
        from repro.core.registry import segment_program_handles
        return segment_program_handles(self, spec, map_fn, mesh,
                                       seg_tasks=seg_tasks, tag=tag)

    def make_segment_fns(self, spec: JobSpec, map_fn: Callable, mesh):
        """(init_fn, segment_fn, finish_fn) — the checkpointable path.

        ``segment_fn(carry, tokens_seg, task_ids_seg, repeats_seg)``
        advances ``segment`` tasks and returns the new carry — the host
        snapshots it between calls (async), which is exactly the paper's
        per-task window sync.
        """
        return memoized(self._programs, ("seg", spec, map_fn, mesh),
                        lambda: self._build_segment_fns(spec, map_fn, mesh))

    def _build_segment_fns(self, spec: JobSpec, map_fn: Callable, mesh):
        if spec.code_rate > 1:
            # the coded engine consumes r-wide column blocks: the feed
            # hands segments whose width is a multiple of r (submit()
            # scales the segment), re-blocked here for the scan
            if spec.stealing:
                def seg(carry, tok, tid, rep):
                    assert tok.shape[0] % spec.code_rate == 0, tok.shape
                    return _coded_steal_segment(spec, map_fn, carry, tok,
                                                tid, rep)
            else:
                def seg(carry, tok, tid, rep):
                    r = spec.code_rate
                    assert tok.shape[0] % r == 0, tok.shape
                    nb = tok.shape[0] // r
                    carry, _ = lax.scan(
                        partial(_coded_step, spec, map_fn), carry,
                        (tok.reshape(nb, r, -1), tid.reshape(nb, r),
                         rep.reshape(nb, r)))
                    return carry
        elif spec.stealing:
            seg = partial(_steal_segment, spec, map_fn)
        else:
            def seg(carry, tok, tid, rep):
                carry, _ = lax.scan(partial(_step, spec, map_fn), carry,
                                    (tok, tid, rep))
                return carry

        def fin(carry):
            if spec.key_lanes == 2:
                keys, vals, overflow, appended = compact_log(carry, spec)
                return (*tree_combine(keys, vals, AXIS, spec.n_procs,
                                      overflow), appended)
            carry = _drain(carry)
            keys, vals, overflow = combine_records(carry.table, spec)
            return tree_combine(keys, vals, AXIS, spec.n_procs, overflow)

        return wrap_segment_fns(mesh, spec, seg, fin)


# -- module-level aliases (pre-registry call sites) -------------------------

def run_job(spec, map_fn, mesh, tokens, task_ids, repeats):
    from repro.core.registry import get_backend
    return get_backend("1s").run_job(spec, map_fn, mesh, tokens, task_ids,
                                     repeats)


def make_segment_fns(spec, map_fn, mesh):
    from repro.core.registry import get_backend
    return get_backend("1s").make_segment_fns(spec, map_fn, mesh)
