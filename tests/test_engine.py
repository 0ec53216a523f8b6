"""Integration tests for the MapReduce engines on an 8-device mesh.

Each test spawns one subprocess with 8 placeholder CPU devices (the main
pytest process keeps the single real device, per the dry-run isolation
rule) and verifies exact results vs a host oracle, through the unified
``submit()/JobHandle`` API.
"""
import pytest

pytestmark = pytest.mark.slow


def test_wordcount_both_backends_exact(devices8):
    out = devices8("""
        import numpy as np
        from collections import Counter
        from repro.core import JobConfig, submit
        from repro.core.usecases import WordCount
        rng = np.random.default_rng(0)
        for VOCAB, N, task, cap in [(1000, 65536, 2048, 1024),
                                    (127, 8192, 512, 64),
                                    (4096, 50000, 1250, 256)]:
            tokens = rng.integers(0, VOCAB, size=N).astype(np.int32)
            oracle = dict(Counter(tokens.tolist()))
            for backend in ("1s", "2s"):
                cfg = JobConfig(usecase=WordCount(vocab=VOCAB),
                                backend=backend, task_size=task,
                                push_cap=cap, n_procs=8)
                res = submit(cfg, tokens).result()
                assert res.records == oracle, (VOCAB, N, backend)
                assert res.n_tasks == (N + task - 1) // task
                assert res.tasks_per_rank.sum() == res.n_tasks
        print("EXACT")
    """)
    assert "EXACT" in out


def test_wordcount_unbalanced_workload_exact(devices8):
    """The paper's imbalance model (footnote 5): a task is *computed*
    ``repeat`` times while its input is read once — so the result must stay
    exactly the balanced result, for both engines. The JobResult must also
    expose the imbalance it ran under."""
    out = devices8("""
        import numpy as np
        from collections import Counter
        from repro.core import JobConfig, submit
        from repro.core.usecases import WordCount
        from repro.data.corpus import imbalance_repeats
        rng = np.random.default_rng(1)
        VOCAB, N, P = 500, 32768, 8
        tokens = rng.integers(0, VOCAB, size=N).astype(np.int32)
        task = 512
        T = N // task // P
        reps = imbalance_repeats(P, T, mode="unbalanced", hot_factor=4,
                                 hot_fraction=0.25)
        assert reps.max() == 4 and reps.min() == 1
        oracle = dict(Counter(tokens.tolist()))
        for backend in ("1s", "2s"):
            cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend=backend,
                            task_size=task, push_cap=2048, n_procs=P)
            res = submit(cfg, tokens, repeats=reps).result()
            assert res.records == oracle, backend
            assert res.imbalance > 1.0
        print("EXACT-UNBALANCED")
    """)
    assert "EXACT-UNBALANCED" in out


def test_backends_agree_and_sorted(devices8):
    out = devices8("""
        import numpy as np
        from repro.core import JobConfig, submit
        from repro.core.usecases import WordCount
        from repro.core.kv import KEY_SENTINEL
        rng = np.random.default_rng(7)
        tokens = rng.integers(0, 300, size=16384).astype(np.int32)
        res = {}
        for backend in ("1s", "2s"):
            cfg = JobConfig(usecase=WordCount(vocab=300), backend=backend,
                            task_size=1024, push_cap=512, n_procs=8)
            r = submit(cfg, tokens).result()
            valid = r.keys != int(KEY_SENTINEL)
            assert (np.diff(r.keys[valid]) > 0).all()  # Combine sorts
            res[backend] = (r.keys[valid].tolist(), r.values[valid].tolist())
        assert res["1s"] == res["2s"]
        print("AGREE")
    """)
    assert "AGREE" in out


def test_push_cap_overflow_ownership_transfer(devices8):
    """With a tiny push_cap most records overflow → stay owner-local and be
    folded during Combine (paper footnote 2). Result must stay exact."""
    out = devices8("""
        import numpy as np
        from collections import Counter
        from repro.core import JobConfig, submit
        from repro.core.usecases import WordCount
        rng = np.random.default_rng(2)
        # skewed keys: heavy hitters overflow the per-owner bucket cap
        tokens = rng.zipf(1.2, size=32768).astype(np.int32) % 100
        tokens = tokens.astype(np.int32)
        oracle = dict(Counter(tokens.tolist()))
        for backend in ("1s", "2s"):
            cfg = JobConfig(usecase=WordCount(vocab=100), backend=backend,
                            task_size=1024, push_cap=4, n_procs=8)
            res = submit(cfg, tokens).result()
            assert res.records == oracle, backend
        print("OVERFLOW-EXACT")
    """)
    assert "OVERFLOW-EXACT" in out


def test_segmented_matches_oneshot_both_backends(devices8):
    """The segmented lifecycle (step()-driven, checkpointable) must equal
    the oneshot result for EVERY backend — the segmented path is part of
    the shared Backend protocol, not a onesided side-door. Includes a
    simulated restart from a mid-job in-memory snapshot."""
    out = devices8("""
        import dataclasses
        import numpy as np, jax
        from collections import Counter
        from repro.core import JobConfig, submit
        from repro.core.usecases import WordCount

        rng = np.random.default_rng(5)
        VOCAB, N, P, task = 400, 32768, 8, 512
        tokens = rng.integers(0, VOCAB, size=N).astype(np.int32)
        oracle = dict(Counter(tokens.tolist()))

        for backend in ("1s", "2s"):
            cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend=backend,
                            task_size=task, push_cap=1024, n_procs=P,
                            segment=2)
            handle = submit(cfg, tokens)
            snapshots = []
            while True:
                more = handle.step()
                snapshots.append((handle.cursor,
                                  jax.tree.map(np.asarray, handle.carry)))
                if not more:
                    break
            res = handle.result()
            assert res.records == oracle, (backend, "segmented != oracle")

            oneshot = submit(dataclasses.replace(cfg, segment=0),
                             tokens).result()
            assert oneshot.records == res.records, backend

            # restart: resume from the first snapshot and replay the rest
            cur0, carry0 = snapshots[0]
            h2 = submit(cfg, tokens).load(carry0, cur0)
            r2 = h2.result()
            assert (r2.keys == res.keys).all(), (backend, "restart keys")
            assert (r2.values == res.values).all(), (backend, "restart vals")
        print("SEGMENTED-EXACT")
    """, timeout=560)
    assert "SEGMENTED-EXACT" in out


def test_new_usecases_both_backends_8dev(devices8):
    """Histogram and InvertedIndex are oracle-exact on the 8-device mesh
    for both backends (scenario diversity through one API)."""
    out = devices8("""
        import numpy as np
        from repro.core import (JobConfig, submit, Histogram, InvertedIndex,
                                histogram_oracle, inverted_index_oracle)
        rng = np.random.default_rng(3)
        VOCAB, N, P, task = 1024, 32768, 8, 512
        tokens = rng.integers(0, VOCAB, size=N).astype(np.int32)
        n_tasks = N // task
        for backend in ("1s", "2s"):
            h = submit(JobConfig(usecase=Histogram(vocab=VOCAB, n_bins=32),
                                 backend=backend, task_size=task,
                                 push_cap=task, n_procs=P), tokens).result()
            assert (h.output == histogram_oracle(tokens, VOCAB, 32)).all()

            q = (5, 99, 512)
            tpd = n_tasks // 4
            uc = InvertedIndex(queries=q, n_docs=4, tasks_per_doc=tpd)
            r = submit(JobConfig(usecase=uc, backend=backend,
                                 task_size=task, push_cap=task,
                                 n_procs=P), tokens).result()
            assert r.output == inverted_index_oracle(
                tokens, q, task, tpd, 4), backend
        print("USECASES-EXACT")
    """)
    assert "USECASES-EXACT" in out


def test_tree_combine_multiproc_sorted_merge(devices8):
    out = devices8("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.core.combine import tree_combine
        from repro.core.kv import KEY_SENTINEL
        from jax import shard_map
        from repro.distributed.mesh import local_mesh
        mesh = local_mesh((8,), ("procs",))
        rng = np.random.default_rng(11)
        # per-proc sorted unique keys; capacity W covers the merged union
        K, W = 32, 256
        keys = np.full((8, W), int(KEY_SENTINEL), np.int32)
        vals = np.zeros((8, W), np.int32)
        oracle = {}
        for p in range(8):
            ks = np.sort(rng.choice(200, size=rng.integers(5, K),
                                    replace=False)).astype(np.int32)
            keys[p, :len(ks)] = ks
            vals[p, :len(ks)] = p + 1
            for k in ks:
                oracle[int(k)] = oracle.get(int(k), 0) + p + 1

        def body(k, v):
            kk, vv, of = tree_combine(k[0], v[0], "procs", 8)
            return kk[None], vv[None], of[None]

        fn = jax.jit(shard_map(body, mesh=mesh,
                               in_specs=(P("procs"), P("procs")),
                               out_specs=(P("procs"), P("procs"),
                                          P("procs"))))
        ok, ov, of = fn(keys, vals)
        ok, ov = np.asarray(ok)[0], np.asarray(ov)[0]
        valid = ok != int(KEY_SENTINEL)
        got = dict(zip(ok[valid].tolist(), ov[valid].tolist()))
        assert got == oracle
        assert (np.diff(ok[valid]) > 0).all()
        # W covers the union: the overflow counter must stay 0 (and be
        # identical on every rank — it is psum-replicated)
        assert (np.asarray(of) == 0).all()
        print("COMBINE-OK")
    """)
    assert "COMBINE-OK" in out


def test_tree_combine_overflow_detected_at_merge_levels(devices8):
    """Satellite bugfix: two full W-wide runs whose key union exceeds W
    used to be truncated to W at each level with the loss vanishing at
    the next — the overflow must now surface, counted globally. Both the
    raw tree (disjoint per-rank runs => every merge overflows) and the
    Job API path (per-rank windows fit combine_capacity, the union does
    not => overflow arises ONLY inside the tree) are pinned."""
    out = devices8("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.combine import tree_combine
        from repro.core.kv import KEY_SENTINEL
        from jax import shard_map
        from repro.distributed.mesh import local_mesh

        mesh = local_mesh((8,), ("procs",))
        W = 16
        # 8 disjoint full runs: rank p owns keys [p*W, (p+1)*W)
        keys = (np.arange(8 * W, dtype=np.int32).reshape(8, W))
        vals = np.ones((8, W), np.int32)

        def body(k, v):
            kk, vv, of = tree_combine(k[0], v[0], "procs", 8)
            return kk[None], vv[None], of[None]

        fn = jax.jit(shard_map(body, mesh=mesh,
                               in_specs=(P("procs"), P("procs")),
                               out_specs=(P("procs"), P("procs"),
                                          P("procs"))))
        ok, ov, of = fn(keys, vals)
        of = np.asarray(of)
        # merges: 4+2+1 = 7, each unions 2W unique keys into W -> W lost
        assert (of == 7 * W).all(), of          # replicated global count
        ok0 = np.asarray(ok)[0]
        assert (ok0 == np.arange(W)).all()      # smallest W keys survive

        # Job API: per-rank windows fit W, only the tree overflows
        from repro.core import (CombineOverflowError, JobConfig, submit,
                                wordcount_oracle)
        from repro.core.usecases import WordCount
        VOCAB = 256
        toks = np.tile(np.arange(VOCAB, dtype=np.int32), 32)  # all keys hot
        oracle = wordcount_oracle(toks, VOCAB)
        cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend="1s",
                        task_size=512, push_cap=512, n_procs=8,
                        combine_capacity=64)
        h = submit(cfg, toks)
        try:
            h.result()
            raise SystemExit("no overflow raised")
        except CombineOverflowError as e:
            assert e.result.combine_overflow > 0
            assert e.result.records != oracle   # pre-fix silent wrongness
            assert len(e.result.records) <= 64
        print("TREE-OVERFLOW-OK")
    """)
    assert "TREE-OVERFLOW-OK" in out


def test_tree_combine_overflow_saturates_past_int31(devices8):
    """Regression at >2^31 synthetic counts: 8 ranks each seeding 2^30
    lost records sum to 2^33 — the old int32 psum wrapped that to
    exactly 0, i.e. a catastrophic loss reported as \"exact\". The
    saturating accumulation must instead pin the total near INT32_MAX,
    identically on every rank."""
    out = devices8("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.combine import SAT_MAX, tree_combine
        from repro.core.kv import KEY_SENTINEL
        from jax import shard_map
        from repro.distributed.mesh import local_mesh
        mesh = local_mesh((8,), ("procs",))
        W = 16
        keys = np.full((8, W), int(KEY_SENTINEL), np.int32)
        vals = np.zeros((8, W), np.int32)

        def body(k, v):
            kk, vv, of = tree_combine(k[0], v[0], "procs", 8,
                                      overflow=jnp.int32(2 ** 30))
            return kk[None], vv[None], of[None]

        fn = jax.jit(shard_map(body, mesh=mesh,
                               in_specs=(P("procs"), P("procs")),
                               out_specs=(P("procs"), P("procs"),
                                          P("procs"))))
        _, _, of = fn(keys, vals)
        of = np.asarray(of)
        # every rank agrees (psum-replicated) ...
        assert (of == of[0]).all(), of
        # ... and the 2^33 true loss saturates (per-rank contributions
        # clamp to SAT_MAX // 8) instead of wrapping to 0
        assert of[0] == 8 * (SAT_MAX // 8), of
        print("SAT-OK", int(of[0]))
    """)
    assert "SAT-OK" in out


def test_sat_add_i32_saturates_instead_of_wrapping():
    import jax.numpy as jnp
    from repro.core.combine import SAT_MAX, sat_add_i32
    a = jnp.int32(SAT_MAX - 5)
    assert int(sat_add_i32(a, jnp.int32(10))) == SAT_MAX
    assert int(sat_add_i32(jnp.int32(3), jnp.int32(4))) == 7
    assert int(sat_add_i32(jnp.int32(0), a)) == SAT_MAX - 5
    # elementwise too (the psum contributions are arrays)
    got = sat_add_i32(jnp.asarray([SAT_MAX, 1], jnp.int32),
                      jnp.asarray([1, 1], jnp.int32))
    assert got.tolist() == [SAT_MAX, 2]
