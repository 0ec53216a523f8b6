"""The engine step's one-sort Local Reduce + bucketize
(``kv.reduce_and_bucketize``) against the reference composition it
replaces: ``bucketize(local_reduce_repeated(...), owners=lookup_owner(
<unique keys>))``. Buckets and counts must be bit-identical, and the
overflow must fold into a window exactly as the reference's does; an
engine's carry after every segment must equal the one the reference
composition gives.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.kv import (KEY_SENTINEL, bucketize, local_reduce_repeated,
                           reduce_and_bucketize)
from repro.core.partition import lookup_owner
from repro.core.windows import DenseWindow

SENT = int(KEY_SENTINEL)
S, V, CAP = 128, 128, 8


def _task(rng, per_owner, P, values="small", split=False):
    """S raw records whose distinct in-window keys give each of the P
    owners ``per_owner`` of them, plus sentinel padding and keys outside
    the window (dropped); returns keys, values and the owner maps."""
    pool = rng.choice(V, size=per_owner * P, replace=False)
    omap = rng.integers(0, P, V).astype(np.int32)
    omap[pool] = np.arange(pool.size) % P
    osplit = np.ones((V,), np.int32)
    if split:
        osplit[rng.random(V) < 0.4] = rng.integers(2, P + 1)
    rest = rng.choice(pool, size=S - pool.size) if pool.size else \
        np.full((S,), SENT)
    rest[rng.random(rest.size) < 0.1] = SENT
    rest[rng.random(rest.size) < 0.05] = V + 3
    keys = rng.permutation(np.concatenate([pool, rest])).astype(np.int32)
    if values == "small":
        vals = rng.integers(1, 100, S)
    elif values == "negative":
        vals = rng.integers(-1000, 50, S)
    else:                                   # "wrap": sums wrap int32
        vals = np.int64(2 ** 31 - 1) - rng.integers(0, 3, S)
    return (keys, vals.astype(np.int32), jnp.asarray(omap),
            jnp.asarray(osplit))


def _assert_same(keys, vals, omap, osplit, task_id, P, rep, cap=CAP):
    keys, vals = jnp.asarray(keys), jnp.asarray(vals)
    n = keys.shape[0]
    tid, rep = jnp.int32(task_id), jnp.int32(rep)
    uk, uv = local_reduce_repeated(keys, vals, n, rep)
    want = bucketize(uk, uv, P, cap,
                     owners=lookup_owner(omap, osplit, uk, tid, P))
    got = jax.jit(reduce_and_bucketize, static_argnums=(3, 4))(
        keys, vals, lookup_owner(omap, osplit, keys, tid, P), P, cap, rep)
    for name, g, w in zip(("keys", "values", "counts"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    assert got[3][0].shape == (n,)
    # both folds of the engine step: the pushed chunk, then the overflow
    table = DenseWindow(jnp.arange(omap.shape[0], dtype=jnp.int32) * 7)
    fold = [table.put(bk.reshape(-1), bv.reshape(-1)).put(*of).table
            for bk, bv, _, of in (got, want)]
    np.testing.assert_array_equal(np.asarray(fold[0]), np.asarray(fold[1]))
    return np.asarray(want[2])


@pytest.mark.parametrize("rep", [1, 3])
@pytest.mark.parametrize("per_owner", [CAP - 3, CAP, CAP + 5],
                         ids=["below-cap", "at-cap", "above-cap"])
@pytest.mark.parametrize("P", [1, 2, 4])
def test_one_sort_matches_the_reference(P, per_owner, rep):
    rng = np.random.default_rng([P, per_owner, rep])
    counts = _assert_same(*_task(rng, per_owner, P), 5, P, rep)
    assert counts.tolist() == [min(per_owner, CAP)] * P


def _uniform_task(rng, n, vocab, P, *, one_key=False, split=False):
    """``n`` raw records drawn uniformly from the window (every record
    one key if ``one_key``), about 15% of them padding, over a random
    owner map (hot keys split over several owners if ``split``)."""
    keys = rng.integers(0, vocab, n).astype(np.int32)
    if one_key:
        keys[:] = keys[0]
    keys[rng.random(n) < 0.15] = SENT
    vals = rng.integers(0, 100, n).astype(np.int32)
    omap = rng.integers(0, P, vocab).astype(np.int32)
    osplit = np.ones((vocab,), np.int32)
    if split and P > 1:
        osplit[rng.random(vocab) < 0.3] = rng.integers(2, P + 1)
    return keys, vals, jnp.asarray(omap), jnp.asarray(osplit)


@pytest.mark.parametrize("rep", [1, 3])
@pytest.mark.parametrize("P", [1, 2, 4])
@pytest.mark.parametrize("case", ["cap-one", "one-key"])
def test_one_record_buckets_and_one_key_tasks_match_the_reference(
        case, P, rep):
    """One-record buckets send every other record of an owner to the
    overflow (ownership transfer); a task of one key reduces to a
    single record pushed to one owner."""
    rng = np.random.default_rng([P, rep, len(case)])
    if case == "cap-one":
        counts = _assert_same(*_uniform_task(rng, 48, 128, P), 3, P, rep,
                              cap=1)
        assert counts.max() <= 1
    else:
        counts = _assert_same(*_uniform_task(rng, 32, 100, P, one_key=True),
                              5, P, rep, cap=4)
        assert counts.sum() == 1


@pytest.mark.parametrize("vocab,n,P,cap,rep", [
    (32, 256, 4, 8, 1),
    (64, 512, 8, 16, 1),
    (64, 500, 8, 16, 2),       # a task that is not a multiple of 128
    (128, 64, 4, 8, 3),
    (16, 2048, 2, 4, 1),       # many records on a tiny window
])
def test_one_sort_matches_the_reference_at_other_shapes(vocab, n, P, cap,
                                                        rep):
    rng = np.random.default_rng([vocab, n, P])
    _assert_same(*_uniform_task(rng, n, vocab, P, split=True), 7, P, rep,
                 cap=cap)


@pytest.mark.parametrize("rep", [1, 3])
def test_an_all_sentinel_task_pushes_nothing(rep):
    rng = np.random.default_rng(1)
    _, vals, omap, osplit = _task(rng, 0, 4)
    keys = np.full((S,), SENT, np.int32)
    counts = _assert_same(keys, vals, omap, osplit, 0, 4, rep)
    assert counts.tolist() == [0, 0, 0, 0]


@pytest.mark.parametrize("task_id", [0, 1, 6, 2 ** 30 + 11])
@pytest.mark.parametrize("P", [2, 4])
def test_split_keys_route_by_task_id(P, task_id):
    rng = np.random.default_rng([P, task_id])
    keys, vals, omap, osplit = _task(rng, CAP, P, split=True)
    assert int(jnp.max(osplit)) > 1
    _assert_same(keys, vals, omap, osplit, task_id, P, 1)


@pytest.mark.parametrize("rep", [1, 3])
@pytest.mark.parametrize("values", ["negative", "wrap"])
def test_negative_and_wrapping_values_stay_int32_exact(values, rep):
    rng = np.random.default_rng([rep, len(values)])
    _assert_same(*_task(rng, CAP + 2, 2, values), 3, 2, rep)


def test_engine_carries_match_the_reference_composition(devices8):
    """A 2-segment job on the 1s, 1s + stealing, 2s and coded r=2
    engines: the carry after each segment equals the one the engines'
    previous composition (local_reduce_repeated -> lookup_owner on the
    unique keys -> bucketize) gives."""
    out = devices8("""
        import jax, numpy as np
        from repro.core import JobConfig, WordCount, get_backend, submit
        from repro.core import kv, onesided, partition, twosided
        from repro.core.planner import plan_input
        from repro.data.corpus import synth_corpus, zipf_skew_repeats

        VOCAB, TASK, CAP, P = 300, 128, 16, 4
        tokens = synth_corpus(4 * P * TASK, VOCAB, seed=2)
        T = plan_input(len(tokens), TASK, P).tasks_per_proc
        reps = zipf_skew_repeats(P, T, 1.2, mean_rep=2, seed=3)

        def ref_lookup(omap, osplit, keys, tid, n_procs):
            return omap, osplit, tid

        def ref_step(keys, vals, owners, n_procs, cap, rep=1):
            omap, osplit, tid = owners
            uk, uv = kv.local_reduce_repeated(keys, vals, keys.shape[0],
                                              rep)
            return kv.bucketize(uk, uv, n_procs, cap,
                                owners=partition.lookup_owner(
                                    omap, osplit, uk, tid, n_procs))

        def carries(cfg):
            for b in ("1s", "2s"):
                get_backend(b)._programs.clear()
            h = submit(cfg, tokens, repeats=reps)
            got = []
            for _ in range(2):
                h.step()
                got.append(jax.tree.map(np.asarray, h.carry))
            h.close()
            return got

        engines = {
            "1s": dict(),
            "1s+sampled+split": dict(partitioner="sampled+split"),
            "1s+stealing": dict(stealing=True),
            "2s": dict(backend="2s"),
            "coded-r2": dict(code_rate=2),
        }
        for name, kw in engines.items():
            cfg = JobConfig(usecase=WordCount(vocab=VOCAB), task_size=TASK,
                            push_cap=CAP, n_procs=P, segment=2, **kw)
            new = carries(cfg)
            saved = {m: (m.lookup_owner, m.reduce_and_bucketize)
                     for m in (onesided, twosided)}
            for m in saved:
                m.lookup_owner, m.reduce_and_bucketize = ref_lookup, ref_step
            try:
                ref = carries(cfg)
            finally:
                for m, fns in saved.items():
                    m.lookup_owner, m.reduce_and_bucketize = fns
            for seg, (a, b) in enumerate(zip(new, ref)):
                for field in a._fields:
                    np.testing.assert_array_equal(
                        getattr(a, field), getattr(b, field),
                        err_msg=f"{name} segment {seg} {field}")
            print("SAME", name)
        print("CARRIES-OK")
    """, n_devices=4)
    assert "CARRIES-OK" in out, out


@pytest.mark.parametrize("n", [1, 5, 128, 129, 300, 4096])
def test_prefix_scans_match_numpy(n):
    from repro.core.kv import _scan
    rng = np.random.default_rng(n)
    x = rng.integers(0, 2 ** 31 // max(n, 1), (3, n)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(_scan(jnp.asarray(x), jnp.add)),
                                  np.cumsum(x, axis=1))
    np.testing.assert_array_equal(
        np.asarray(_scan(jnp.asarray(x), jnp.maximum)),
        np.maximum.accumulate(x, axis=1))
