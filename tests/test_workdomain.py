"""Cross-job co-scheduling (repro/core/workdomain.py + the fleet-wide
cursor in repro/core/steal.py).

Pins the tentpole contract of the WorkDomain: the fleet cursor claims
every (job, task) pair exactly once across job boundaries; a
single-member fleet reduces bit-identically to the solo steal schedule;
every co-scheduled member's records are bit-identical to its solo run
(including across a mid-co-schedule fleet checkpoint/restore); and the
scheduler charges tenants the work their jobs actually *executed* in
mixed slices, not what a slice was nominally assigned.
"""
import numpy as np
import pytest

from repro.core import JobConfig, JobScheduler, submit
from repro.core.scheduler import DONE
from repro.core.steal import composite_slots, fleet_merge, steal_schedule
from repro.core.usecases import Histogram, WordCount, wordcount_oracle
from repro.core.workdomain import WorkDomain, can_coschedule

VOCAB, TASK = 200, 512
STRIDE = 64                     # composite id stride for host-level tests


def random_grid(rng, P, max_t=8):
    """Random member assignment grid (same shape family as
    test_steal.random_grid): unique local ids < STRIDE, right-padded."""
    T = int(rng.integers(1, max_t + 1))
    counts = rng.integers(0, T + 1, size=P)
    if counts.sum() == 0:
        counts[int(rng.integers(0, P))] = 1
    ids = -np.ones((P, T), np.int32)
    pool = rng.permutation(STRIDE)[: int(counts.sum())]
    k = 0
    for r in range(P):
        ids[r, : counts[r]] = pool[k: k + counts[r]]
        k += counts[r]
    reps = rng.integers(1, 9, size=(P, T)).astype(np.int32)
    return ids, reps


def wc_cfg(**kw):
    base = dict(usecase=WordCount(vocab=VOCAB), backend="1s",
                task_size=TASK, push_cap=256, n_procs=1, segment=1)
    base.update(kw)
    return JobConfig(**base)


# ---------------------------------------------------------------------------
# fleet-wide cursor: exactly-once across job boundaries, solo reduction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("P", [2, 4, 8])
def test_fleet_exactly_once_across_jobs(P):
    """Property: over random K-member grids and random initial progress,
    the fleet cursor executes every (job, task) pair exactly once — the
    solo exactly-once argument survives the composite encoding — and
    the per-slot executed-work split accounts each member's repeats
    exactly (the host twin of ``carry.job_work``)."""
    rng = np.random.default_rng(P)
    for trial in range(15):
        K = int(rng.integers(2, 5))
        members = [random_grid(rng, P) for _ in range(K)]
        ids, reps = fleet_merge([m[0] for m in members],
                                [m[1] for m in members], stride=STRIDE)
        work0 = rng.integers(0, 40, size=P).astype(np.int32)
        sched = steal_schedule(ids, reps, work0=work0,
                               coslots=K, costride=STRIDE)
        executed = sched.exec_ids[sched.exec_ids >= 0]
        expect = [j * STRIDE + t for j, (g, _) in enumerate(members)
                  for t in g[g >= 0].tolist()]
        assert sorted(executed.tolist()) == sorted(expect), (
            f"P={P} trial={trial}: fleet cursor lost/duplicated a task")
        for j, (g, r) in enumerate(members):
            assert sched.slot_work[j] == int(r[g >= 0].sum()), (
                f"P={P} trial={trial}: slot {j} mis-accounted")
        assert int(sched.slot_work.sum()) == int(
            (sched.work - work0).sum())


def test_single_member_fleet_reduces_to_solo():
    """A 1-member fleet is the solo schedule bit-for-bit — merging is an
    encoding, not a different scheduler."""
    rng = np.random.default_rng(42)
    for _ in range(10):
        ids, reps = random_grid(rng, 4)
        solo = steal_schedule(ids, reps)
        fids, freps = fleet_merge([ids], [reps], stride=STRIDE)
        fleet = steal_schedule(fids, freps, coslots=1, costride=STRIDE)
        np.testing.assert_array_equal(
            solo.exec_ids[solo.exec_ids >= 0],
            fleet.exec_ids[fleet.exec_ids >= 0])
        np.testing.assert_array_equal(solo.work, fleet.work)
        np.testing.assert_array_equal(solo.stolen, fleet.stolen)


def test_priority_lanes_come_first():
    """A higher-priority member's columns sit at the head of every
    rank's deque — claimed (and stolen) before any lower lane."""
    lo = np.arange(8, dtype=np.int32).reshape(2, 4)
    hi = np.arange(6, dtype=np.int32).reshape(2, 3)
    ones = [np.ones_like(lo), np.ones_like(hi)]
    ids, _ = fleet_merge([lo, hi], ones, stride=STRIDE,
                         priorities=[0, 7])
    slots = composite_slots(ids, STRIDE)
    for r in range(2):
        row = slots[r][slots[r] >= 0]
        first_lo = np.argmax(row == 0)
        assert (row[:first_lo] == 1).all(), f"rank {r}: {row}"


def test_fleet_merge_rejects_oversized_ids():
    ids = np.array([[0, STRIDE]], np.int32)     # id == stride: overflow
    with pytest.raises(AssertionError, match="stride"):
        fleet_merge([ids], [np.ones_like(ids)], stride=STRIDE)


# ---------------------------------------------------------------------------
# eligibility gates: '2s' / sampling cleanly reject
# ---------------------------------------------------------------------------

def test_twosided_rejects_composite_spec():
    from repro.core.registry import JobSpec, get_backend
    spec = JobSpec(vocab=VOCAB, task_size=TASK, push_cap=256, n_procs=1,
                   segment=1, coslots=2, costride=STRIDE)
    with pytest.raises(ValueError, match="'2s'.*coslots"):
        get_backend("2s").make_segment_fns(
            spec, lambda t, i, r: (t, t), None)


def test_can_coschedule_gates(tokens):
    h = submit(wc_cfg(), tokens)
    assert can_coschedule(h)
    oneshot = submit(wc_cfg(segment=0), tokens)
    assert not can_coschedule(oneshot)
    two_s = submit(wc_cfg(backend="2s"), tokens)
    assert not can_coschedule(two_s)
    sampled = submit(wc_cfg(partitioner="sampled"), tokens)
    assert not can_coschedule(sampled)
    for x in (h, oneshot, two_s, sampled):
        x.feed.close()


def test_workdomain_needs_two_members(tokens):
    h = submit(wc_cfg(), tokens)
    with pytest.raises(ValueError, match="at least two"):
        WorkDomain([h])
    h.feed.close()


# ---------------------------------------------------------------------------
# scheduler integration: record identity, executed-work fair share,
# mid-co-schedule fleet checkpoint/restore
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(0)
    return rng.integers(0, VOCAB, size=13 * TASK).astype(np.int32)


@pytest.fixture(scope="module")
def tokens_b():
    rng = np.random.default_rng(1)
    return rng.integers(0, VOCAB, size=7 * TASK).astype(np.int32)


def test_coscheduled_jobs_record_identical_to_solo(tokens, tokens_b):
    ref_a = submit(wc_cfg(), tokens).result()
    ref_b = submit(wc_cfg(), tokens_b).result()
    sched = JobScheduler(coschedule=True)
    ha = sched.submit(wc_cfg(), tokens, tenant="t", name="a")
    hb = sched.submit(wc_cfg(), tokens_b, tenant="t", name="b")
    sched.run_until_complete()
    assert len(sched._domains) == 1 and sched._domains[0].done
    for h, ref in ((ha, ref_a), (hb, ref_b)):
        got = h.result()
        assert got.records == ref.records
        assert got.output == ref.output
    # executed work charged per member: one task-rep per task here
    assert sched._by_name["a"].work_done == 13
    assert sched._by_name["b"].work_done == 7
    assert sched.tenants["t"].work == 20


def test_short_member_finalizes_before_domain_drains(tokens, tokens_b):
    """Operation-level co-scheduling must not hold a short job's result
    hostage to a long co-tenant: member b (7 tasks) adopts its result
    while the domain is still executing member a (13 tasks)."""
    sched = JobScheduler(coschedule=True)
    sched.submit(wc_cfg(), tokens, tenant="t", name="a")
    sched.submit(wc_cfg(), tokens_b, tenant="t", name="b")
    states = []
    for _ in range(64):
        sched.run_until_complete(max_slices=1)
        states.append(tuple(j.state for j in sched.jobs))
        if all(j.state == DONE for j in sched.jobs):
            break
    assert states[-1] == (DONE, DONE), states
    assert ("live", DONE) in states, states


def test_fairshare_charges_executed_not_assigned(tokens, tokens_b, tokens_c):
    """Satellite regression: tenant A's two co-schedulable jobs execute
    20 task-reps total; tenant B's solo histogram job executes 20 too.
    Fair share must end with the tenants' charged service equal (within
    10%) — charging assigned slices instead of executed work would skew
    A by ~2x (each domain slice advances both members)."""
    sched = JobScheduler(policy="fair", coschedule=True)
    sched.submit(wc_cfg(), tokens, tenant="A", name="a1")
    sched.submit(wc_cfg(), tokens_b, tenant="A", name="a2")
    sched.submit(JobConfig(usecase=Histogram(vocab=VOCAB, n_bins=16),
                           backend="1s", task_size=TASK, push_cap=256,
                           n_procs=1, segment=1),
                 tokens_c, tenant="B", name="b1")
    sched.run_until_complete()
    assert len(sched._domains) == 1          # histogram sliced solo
    wa, wb = sched.tenants["A"].work, sched.tenants["B"].work
    assert wa == 20 and wb == 20, (wa, wb)
    assert abs(wa - wb) <= 0.1 * max(wa, wb)


@pytest.fixture(scope="module")
def tokens_c():
    rng = np.random.default_rng(2)
    return rng.integers(0, VOCAB, size=20 * TASK).astype(np.int32)


def test_mid_coschedule_checkpoint_restore(tokens, tokens_b, tmp_path):
    """Fleet snapshot taken while the shared cursor is mid-domain:
    restore into a fresh scheduler (same submissions) and finish —
    records identical to the uninterrupted solo runs, accounting
    resumes, and the domain re-forms from the manifest."""
    ref_a = submit(wc_cfg(), tokens).result()
    ref_b = submit(wc_cfg(), tokens_b).result()

    s1 = JobScheduler(coschedule=True)
    s1.submit(wc_cfg(), tokens, tenant="t", name="a")
    s1.submit(wc_cfg(), tokens_b, tenant="t", name="b")
    s1.run_until_complete(max_slices=1)
    assert s1._domains and not s1._domains[0].done
    s1.checkpoint(str(tmp_path))

    s2 = JobScheduler(coschedule=True)
    ha = s2.submit(wc_cfg(), tokens, tenant="t", name="a")
    hb = s2.submit(wc_cfg(), tokens_b, tenant="t", name="b")
    s2.restore(str(tmp_path))
    assert len(s2._domains) == 1             # re-formed from manifest
    s2.run_until_complete()
    assert ha.result().records == ref_a.records
    assert hb.result().records == ref_b.records
    assert s2.tenants["t"].work == 20


def test_evicting_live_domain_member_raises(tokens, tokens_b):
    sched = JobScheduler(coschedule=True)
    sched.submit(wc_cfg(), tokens, tenant="t", name="a")
    sched.submit(wc_cfg(), tokens_b, tenant="t", name="b")
    sched.run_until_complete(max_slices=1)
    assert not sched._domains[0].done
    with pytest.raises(RuntimeError, match="co-scheduled"):
        sched.evict("a")
    sched.close()


# ---------------------------------------------------------------------------
# multi-rank: cross-job steals happen, device == host replay, exactness
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multirank_crossjob_stealing_exact(devices8):
    devices8("""
        import numpy as np
        from repro.core.job import JobConfig, submit
        from repro.core.steal import fleet_merge, steal_schedule
        from repro.core.usecases import WordCount
        from repro.core.workdomain import WorkDomain
        from repro.distributed.mesh import local_mesh

        P, S, V = 4, 64, 512
        rng = np.random.default_rng(0)
        sizes = (13, 7)
        data = [rng.integers(0, V, size=n * S).astype(np.int32)
                for n in sizes]
        reps = [np.where(rng.random((P, -(-n // P))) < 0.3, 5, 1)
                .astype(np.int32) for n in sizes]
        cfg = JobConfig(usecase=WordCount(vocab=V), backend="1s",
                        task_size=S, push_cap=128, n_procs=P, segment=1,
                        stealing=True)
        mesh = local_mesh((P,), ("procs",))

        solo = [submit(cfg, d, mesh=mesh, repeats=r).result()
                for d, r in zip(data, reps)]

        h0 = submit(cfg, data[0], mesh=mesh, repeats=reps[0])
        h1 = submit(cfg, data[1], mesh=mesh, repeats=reps[1])
        dom = WorkDomain([h0, h1], names=["a", "b"], mesh=mesh)
        while dom.step(1):
            dom.collect_finished()
        dom.collect_finished()
        assert dom.done
        carry = dom.handle._carry
        stolen = np.asarray(carry.stolen)[0]
        assert stolen.sum() > 0, "no cross-rank steals in skewed fleet"

        # every member bit-identical to its solo run
        for h, ref, name in zip([h0, h1], solo, "ab"):
            got = h.result()
            assert got.records == ref.records, name
            assert got.output == ref.output, name

        # host replay, chained segment-by-segment exactly as the device
        # stepped (work0 carries the progress row across segments),
        # reproduces both carry rows bit-for-bit
        ids = dom.handle.feed.task_ids_grid
        rg = dom.handle.feed.repeats_grid
        seg = dom.handle.feed.segment
        slot_work = np.zeros((dom.K,), np.int64)
        work = np.zeros((P,), np.int32)
        for c0 in range(0, ids.shape[1], seg):
            sch = steal_schedule(ids[:, c0:c0 + seg], rg[:, c0:c0 + seg],
                                 work0=work, coslots=dom.K,
                                 costride=dom.stride)
            work = sch.work
            slot_work += sch.slot_work
        np.testing.assert_array_equal(
            slot_work, np.asarray(carry.job_work)[0])
        np.testing.assert_array_equal(work, np.asarray(carry.work)[0])
        print("CROSSJOB-OK", stolen.tolist(), slot_work.tolist())
    """)
