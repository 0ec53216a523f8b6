"""PUMA Inverted-Index (``PostingsIndex``) through ``submit`` against a
plain count: (word, document) keys in two lanes through the one-sided
engine's log window, on one device in this process and on four CPU
devices in a child process; the log's record count, the phase scopes of
the programs that ran, the checkpoint round trip, and the settings a
two-lane job is refused under."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CombineOverflowError, JobConfig, PostingsIndex,
                        WordCount, submit)
from repro.core.kv import KEY_SENTINEL, sort_reduce

SENT = int(KEY_SENTINEL)
VOCAB, N, TASK = 1 << 22, 5000, 512


def plain_count(tokens, doc_len: int) -> dict:
    """{(word << 32) | document: count}, one token at a time."""
    out = {}
    for pos, word in enumerate(np.asarray(tokens).tolist()):
        if word != SENT:
            key = (word << 32) | (pos // doc_len)
            out[key] = out.get(key, 0) + 1
    return out


def pairs_per_task(tokens, doc_len: int, task: int) -> int:
    """Distinct (word, document) pairs of each task, summed: the records
    the tasks append to the log."""
    docs = np.arange(len(tokens)) // doc_len
    return sum(len(set(zip(tokens[i:i + task].tolist(),
                           docs[i:i + task].tolist())))
               for i in range(0, len(tokens), task))


@pytest.fixture(scope="module")
def tokens():
    """N Zipf-like word ids over 2^22, a tenth of them at or above 2^19
    (where a packed 32-bit (word, document) key would wrap), and one word
    planted on both sides of a task boundary inside one document. N is
    not a multiple of the task size, so the last task is padded."""
    rng = np.random.default_rng(0)
    t = (rng.zipf(1.1, N) % VOCAB).astype(np.int32)
    high = rng.random(N) < 0.1
    t[high] = rng.integers(1 << 19, VOCAB, high.sum())
    t[[500, 520]] = 7          # document 1 (300..599) spans tasks 0 and 1
    return t


def _config(**kw):
    base = dict(usecase=PostingsIndex(vocab=VOCAB, doc_len=300),
                backend="1s", task_size=TASK, push_cap=64, n_procs=1)
    return JobConfig(**dict(base, **kw))


def _run(cfg, tokens):
    h = submit(cfg, tokens)
    if cfg.segment:
        while h.step():
            pass
    return h, h.result()


@pytest.mark.parametrize("doc_len", [300, 1000])
@pytest.mark.parametrize("segment", [1, 8, 0])
def test_records_equal_a_plain_count(tokens, doc_len, segment):
    # 300 does not divide the task size; 1000-token documents span two
    # or three tasks; segments of 1 task cut every document of two tasks
    cfg = _config(usecase=PostingsIndex(vocab=VOCAB, doc_len=doc_len),
                  segment=segment)
    _, res = _run(cfg, tokens)
    want = plain_count(tokens, doc_len)
    assert res.records == want
    assert list(res.records) == sorted(want)
    assert res.records[(7 << 32) | (500 // doc_len)] >= 2
    assert any(k >> 32 >= 1 << 19 for k in res.records)
    assert res.log_records == pairs_per_task(tokens, doc_len, TASK)
    assert res.combine_overflow == 0


def test_output_is_csr_postings(tokens):
    _, res = _run(_config(segment=8), tokens)
    post = res.output
    assert post.offsets[0] == 0 and post.offsets[-1] == len(res.records)
    assert (np.diff(post.words) > 0).all()
    got = {}
    for i, word in enumerate(post.words.tolist()):
        lo, hi = post.offsets[i], post.offsets[i + 1]
        assert (np.diff(post.docs[lo:hi]) > 0).all()
        for d, f in zip(post.docs[lo:hi].tolist(),
                        post.freqs[lo:hi].tolist()):
            got[(word << 32) | d] = f
    assert got == res.records
    # unsorted records give the same postings
    shuffled = dict(reversed(list(res.records.items())))
    again = PostingsIndex(vocab=VOCAB, doc_len=300).finalize(shuffled)
    for a, b in zip(again, post):
        np.testing.assert_array_equal(a, b)
    empty = PostingsIndex(vocab=VOCAB, doc_len=300).finalize({})
    assert empty.offsets.tolist() == [0] and empty.words.size == 0


def test_undersized_combine_capacity_raises(tokens):
    want = plain_count(tokens, 300)
    h = submit(_config(segment=8, combine_capacity=100), tokens)
    with pytest.raises(CombineOverflowError) as err:
        while h.step():
            pass
        h.result()
    assert err.value.result.combine_overflow == len(want) - 100
    assert len(err.value.result.records) == 100


def test_phase_scopes_of_the_programs_that_ran(tokens):
    h, _ = _run(_config(segment=8), tokens)
    scopes = {role: {s for path in h.trace.op_scopes(role).values()
                     for s in path.split("/")}
              for role in ("segment", "finish")}
    assert {"map", "route", "local_reduce", "log_append"} <= \
        scopes["segment"]
    assert "fold" not in scopes["segment"]
    assert "compact" in scopes["finish"]


def test_checkpoint_restore_round_trips_the_log(tokens, tmp_path):
    from repro.ckpt import CheckpointManager
    cfg = _config(segment=2)
    mgr = CheckpointManager(str(tmp_path), keep=2)
    h = submit(cfg, tokens)
    h.step()
    h.step()
    h.checkpoint(mgr)
    mgr.wait()
    h.close()
    resumed = submit(cfg, tokens).restore(mgr)
    assert resumed.cursor == 4
    while resumed.step():
        pass
    res = resumed.result()
    assert res.records == plain_count(tokens, 300)
    assert res.log_records == pairs_per_task(tokens, 300, TASK)


def _coschedule(tokens):
    from repro.core import WorkDomain
    handles = [submit(_config(segment=1), tokens) for _ in range(2)]
    WorkDomain(handles)


@pytest.mark.parametrize("refused", [
    dict(backend="2s"), dict(partitioner="sampled"), dict(code_rate=2),
    dict(fused_map=True), _coschedule],
    ids=["backend-2s", "sampled", "coded", "fused_map", "workdomain"])
def test_two_lane_jobs_are_refused(tokens, refused):
    with pytest.raises(ValueError, match="two-lane"):
        if callable(refused):
            refused(tokens)
        else:
            submit(_config(**refused), tokens)


def test_dense_window_paths_are_refused(tokens):
    h = submit(_config(segment=2), tokens)
    h.step()
    for call in (h.windows, lambda: h.replan(h.feed.task_ids_grid)):
        with pytest.raises(ValueError, match="two-lane"):
            call()
    h.close()


def test_one_shot_engine_and_lane_counts_are_refused():
    from repro.core import onesided
    from repro.core.registry import JobSpec
    with pytest.raises(ValueError, match="key_lanes"):
        JobSpec(vocab=16, task_size=8, push_cap=4, n_procs=1, key_lanes=3)
    spec = JobSpec(vocab=16, task_size=8, push_cap=4, n_procs=1,
                   key_lanes=2, log_steps=1)
    with pytest.raises(ValueError, match="two-lane"):
        onesided.run_job(spec, None, None, None, None, None)


@pytest.mark.parametrize("capacity", [4, 64])
def test_sort_reduce_of_two_lanes(capacity):
    rng = np.random.default_rng(capacity)
    major = rng.integers(0, 5, 40).astype(np.int32)
    minor = rng.integers(-3, 4, 40).astype(np.int32)
    vals = rng.integers(-9, 9, 40).astype(np.int32)
    major[::7] = SENT
    (gm, gn), gv, n = jax.jit(sort_reduce, static_argnums=2)(
        (jnp.asarray(major), jnp.asarray(minor)), jnp.asarray(vals),
        capacity)
    want = {}
    for a, b, v in zip(major.tolist(), minor.tolist(), vals.tolist()):
        if a != SENT:
            want[(a, b)] = want.get((a, b), 0) + v
    keys = sorted(want)
    assert int(n) == len(keys)
    k = min(capacity, len(keys))
    assert list(zip(np.asarray(gm)[:k].tolist(),
                    np.asarray(gn)[:k].tolist())) == keys[:k]
    assert np.asarray(gv)[:k].tolist() == [want[x] for x in keys[:k]]
    assert (np.asarray(gm)[k:] == SENT).all()
    assert (np.asarray(gv)[k:] == 0).all()


def test_one_lane_jobs_report_no_log(tokens):
    res = submit(JobConfig(usecase=WordCount(vocab=VOCAB), task_size=TASK,
                           push_cap=64, n_procs=1), tokens).result()
    assert res.log_records == 0


def test_four_devices_stealing_off_and_on(devices8, tokens):
    # P=4, hash partitioner; with stealing, rank 0 computes each of its
    # tasks four times, so its peers take tasks from it
    out = devices8(f"""
        import json
        import numpy as np
        from repro.core import JobConfig, PostingsIndex, submit
        tokens = np.asarray({tokens.tolist()!r}, np.int32)
        got = {{}}
        for stealing in (False, True):
            for segment in (1, 8):
                cfg = JobConfig(usecase=PostingsIndex(vocab={VOCAB},
                                                      doc_len=300),
                                backend="1s", task_size={TASK}, push_cap=64,
                                n_procs=4, segment=segment,
                                stealing=stealing)
                T = -(-(-(-len(tokens) // {TASK})) // 4)
                reps = np.ones((4, T), np.int32)
                reps[0] = 4
                h = submit(cfg, tokens, repeats=reps)
                while h.step():
                    pass
                res = h.result()
                got[f"{{stealing}}/{{segment}}"] = [
                    [[k, v] for k, v in res.records.items()],
                    res.log_records, res.n_steals]
        print(json.dumps(got))
    """, n_devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    want = plain_count(tokens, 300)
    assert len(got) == 4
    for name, (records, log_records, steals) in got.items():
        assert dict(map(tuple, records)) == want, name
        assert log_records == pairs_per_task(tokens, 300, TASK), name
        assert (steals > 0) == name.startswith("True"), name
