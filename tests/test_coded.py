"""Coded shuffle (core/coded.py, collectives.coded_exchange, the 1S
coded engine path): grid replication properties, the bytes model,
config validation, and end-to-end exactness.

Load-bearing properties pinned here:

  * :func:`replicate_grids` puts the IDENTICAL row on every member of a
    code group, covers each original task exactly r times, and carries
    repeats/padding with their task — the structure the XOR decode's
    side-information argument rests on;
  * the bytes model states the multicast accounting fig15 gates on:
    P-1 blocks at r=1 vs 1 + (P/r - 1) at r>1;
  * ``JobSpec`` rejects every composition the decode cannot survive
    (indivisible P, co-scheduling) and ``submit`` rejects
    backends that never advertised ``supports_coded``;
  * the full exactness matrix — r ∈ {1,2,3} × partitioner × stealing,
    over skewed repeats on array, mmap, and zipf sources — is
    record-identical to the r=1 run and the host oracle (slow,
    6-device subprocess);
  * an r=2 job checkpointed mid-stream restores and finishes exact, a
    code_rate-mismatched restore fails loudly, and ``replan()`` refuses
    coded handles (slow, 2-device subprocess).
"""
import numpy as np
import pytest

from repro.core import JobConfig, submit
from repro.core.coded import (RECORD_BYTES, group_of, member_of,
                              replicate_grids, shuffle_blocks_per_step,
                              shuffle_bytes)
from repro.core.registry import JobSpec
from repro.core.usecases import WordCount


# ---------------------------------------------------------------------------
# replicate_grids: the host half of the code-group contract
# ---------------------------------------------------------------------------

def test_group_math():
    assert [group_of(q, 2) for q in range(6)] == [0, 0, 1, 1, 2, 2]
    assert [member_of(q, 2) for q in range(6)] == [0, 1, 0, 1, 0, 1]
    assert [group_of(q, 3) for q in range(6)] == [0, 0, 0, 1, 1, 1]


def test_replicate_grids_r1_is_identity():
    ids = np.arange(12, dtype=np.int32).reshape(4, 3)
    reps = np.full((4, 3), 2, np.int32)
    out_ids, out_reps = replicate_grids(ids, reps, 1)
    np.testing.assert_array_equal(out_ids, ids)
    np.testing.assert_array_equal(out_reps, reps)


@pytest.mark.parametrize("P,r", [(6, 2), (6, 3), (4, 2), (8, 4)])
def test_replicate_grids_structure(P, r):
    """Every member of a group carries the identical (P, T*r) row; block
    k of group g is the members' original column-k tasks in rank order;
    each real task id appears exactly r times fleet-wide."""
    rng = np.random.default_rng(P * 10 + r)
    T = 5
    ids = np.arange(P * T, dtype=np.int32).reshape(P, T)
    reps = rng.integers(1, 9, size=(P, T)).astype(np.int32)
    out_ids, out_reps = replicate_grids(ids, reps, r)
    assert out_ids.shape == out_reps.shape == (P, T * r)
    by_task = dict(zip(ids.ravel(), reps.ravel()))
    for g in range(P // r):
        rows = range(g * r, (g + 1) * r)
        for q in rows:
            np.testing.assert_array_equal(out_ids[q], out_ids[g * r])
            np.testing.assert_array_equal(out_reps[q], out_reps[g * r])
        for k in range(T):
            block = out_ids[g * r, k * r:(k + 1) * r]
            np.testing.assert_array_equal(
                block, [ids[q, k] for q in rows])
            # repeats travel with their task
            for j, q in enumerate(rows):
                assert out_reps[g * r, k * r + j] == by_task[ids[q, k]]
    # exactly-r coverage, counting each group's shared row once
    flat = np.concatenate([out_ids[g * r] for g in range(P // r)])
    counts = np.bincount(flat, minlength=P * T)
    np.testing.assert_array_equal(counts, np.full(P * T, 1))
    assert all((out_ids == tid).sum() == r for tid in ids.ravel())


def test_replicate_grids_replicates_padding():
    ids = np.array([[0, 1], [2, -1]], np.int32)
    reps = np.ones((2, 2), np.int32)
    out_ids, _ = replicate_grids(ids, reps, 2)
    # block 1 of the single group is [ids[0,1], ids[1,1]] = [1, -1]
    np.testing.assert_array_equal(out_ids[0], [0, 2, 1, -1])
    np.testing.assert_array_equal(out_ids[1], out_ids[0])


def test_replicate_grids_rejects_indivisible_fleet():
    ids = np.zeros((5, 2), np.int32)
    with pytest.raises(ValueError, match="divisible"):
        replicate_grids(ids, np.ones_like(ids), 2)


# ---------------------------------------------------------------------------
# bytes model: the accounting fig15's CI gate rests on
# ---------------------------------------------------------------------------

def test_shuffle_blocks_per_step():
    # r=1: one unicast bucket per peer
    assert shuffle_blocks_per_step(6, 1) == 5
    # r>1: one coded multicast block + one bucket per spoken-for group
    assert shuffle_blocks_per_step(6, 2) == 3      # ratio 0.60
    assert shuffle_blocks_per_step(6, 3) == 2      # ratio 0.40
    assert shuffle_blocks_per_step(8, 2) == 4
    assert shuffle_blocks_per_step(4, 4) == 1      # one group: XOR only


def test_shuffle_bytes_scales_linearly():
    got = shuffle_bytes(6, 10, 1024, 2)
    assert got == 6 * 10 * 3 * 1024 * RECORD_BYTES
    # the coded win is the blocks ratio, independent of steps/cap
    r1 = shuffle_bytes(6, 7, 512, 1)
    r2 = shuffle_bytes(6, 7, 512, 2)
    assert r2 / r1 == pytest.approx(3 / 5)


# ---------------------------------------------------------------------------
# validation: every composition the decode cannot survive fails loudly
# ---------------------------------------------------------------------------

def _spec(**kw):
    base = dict(vocab=64, task_size=8, push_cap=8, n_procs=4)
    base.update(kw)
    return JobSpec(**base)


def test_jobspec_rejects_bad_code_rates():
    with pytest.raises(ValueError, match="code_rate"):
        _spec(code_rate=0)
    with pytest.raises(ValueError, match="divisible"):
        _spec(n_procs=6, code_rate=4)
    with pytest.raises(ValueError, match="coslots"):
        _spec(code_rate=2, coslots=2, costride=16)
    assert _spec(n_procs=6, code_rate=3).code_rate == 3


def test_submit_rejects_backend_without_coded_support():
    tokens = np.zeros(64, np.int32)
    cfg = JobConfig(usecase=WordCount(vocab=32), backend="2s",
                    task_size=16, push_cap=16, n_procs=1, code_rate=2)
    with pytest.raises(ValueError, match="supports_coded"):
        submit(cfg, tokens)


# ---------------------------------------------------------------------------
# multi-rank exactness matrix + checkpoint round-trip (subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_coded_exactness_matrix(devices8, tmp_path):
    """r ∈ {1,2,3} × partitioner × stealing over skewed repeats, plus
    mmap- and zipf-sourced arms: every coded run is record-identical to
    the r=1 reference and the host oracle."""
    out = devices8(f"""
        import collections
        import numpy as np
        from repro.core import JobConfig, submit
        from repro.core.planner import plan_input
        from repro.core.usecases import WordCount
        from repro.data.corpus import synth_corpus, zipf_skew_repeats
        from repro.data.source import MmapTokenSource, ZipfSource, read_all

        VOCAB, N, TASK, P = 600, 24576, 512, 6
        tokens = synth_corpus(N, VOCAB, seed=0)
        oracle = dict(collections.Counter(np.asarray(tokens).tolist()))
        T = plan_input(N, TASK, P).tasks_per_proc
        reps = zipf_skew_repeats(P, T, 1.4, mean_rep=3, seed=1)

        def run(src, r, part="hash", stealing=False):
            cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend="1s",
                            task_size=TASK, push_cap=512, n_procs=P,
                            partitioner=part, stealing=stealing,
                            code_rate=r)
            return submit(cfg, src, repeats=reps).result()

        base = run(tokens, 1)
        assert base.records == oracle
        checked = 0
        for r in (2, 3):
            for part in ("hash", "sampled+split"):
                for stealing in (False, True):
                    res = run(tokens, r, part, stealing)
                    assert res.records == base.records == oracle, (
                        r, part, stealing)
                    checked += 1
        # skewed + stolen coded run really steals, at group granularity
        stolen = run(tokens, 3, stealing=True)
        assert stolen.n_steals > 0
        w = stolen.work_per_rank.reshape(-1, 3)
        assert (w == w[:, :1]).all(), w    # members of a group agree

        path = {str(tmp_path)!r} + "/coded.bin"
        np.asarray(tokens).tofile(path)
        res = run(MmapTokenSource(path), 2, stealing=True)
        assert res.records == oracle
        checked += 1

        zsrc = ZipfSource(N, vocab=VOCAB, seed=4)
        zoracle = dict(collections.Counter(
            np.asarray(read_all(zsrc)).tolist()))
        assert run(zsrc, 1).records == zoracle
        res = run(ZipfSource(N, vocab=VOCAB, seed=4), 3)
        assert res.records == zoracle
        checked += 1
        print("CODED-OK", checked, int(stolen.n_steals))
    """, n_devices=6)
    assert "CODED-OK" in out


@pytest.mark.slow
def test_coded_checkpoint_round_trip_and_guards(devices8, tmp_path):
    """An r=2 job snapshotted mid-stream restores and finishes exact;
    restoring the snapshot into an r=1 handle fails loudly; replan()
    refuses coded handles outright."""
    out = devices8(f"""
        import collections
        import numpy as np
        import pytest
        from repro.core import JobConfig, submit
        from repro.core.usecases import WordCount
        from repro.data.corpus import synth_corpus
        from repro.ckpt.checkpoint import CheckpointManager

        VOCAB, N, TASK, P = 300, 8192, 256, 2
        tokens = synth_corpus(N, VOCAB, seed=3)
        oracle = dict(collections.Counter(np.asarray(tokens).tolist()))

        def cfg(r, segment=0):
            return JobConfig(usecase=WordCount(vocab=VOCAB), backend="1s",
                             task_size=TASK, push_cap=256, n_procs=P,
                             segment=segment, code_rate=r)

        mgr = CheckpointManager({str(tmp_path)!r} + "/ck")
        h = submit(cfg(2, segment=2), tokens)
        h.step()
        h.checkpoint(mgr)
        mgr.wait()
        _, extra = mgr.peek()
        assert extra["code_rate"] == 2
        h2 = submit(cfg(2, segment=2), tokens).restore(mgr)
        assert h2.result().records == oracle

        with pytest.raises(ValueError, match="code_rate"):
            submit(cfg(1, segment=2), tokens).restore(mgr)

        with pytest.raises(ValueError, match="code_rate"):
            submit(cfg(2, segment=2), tokens).replan(
                np.zeros((P, 1), np.int32))
        print("CKPT-OK")
    """, n_devices=2)
    assert "CKPT-OK" in out
