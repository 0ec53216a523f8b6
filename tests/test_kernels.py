"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels are TPU-targeted (pl.pallas_call + BlockSpec); on this CPU container
they execute via ``interpret=True`` (the kernel body runs in Python), which
validates the block decomposition, masking and online-softmax logic exactly.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.flash_decode import ops as fd_ops, ref as fd_ref
from repro.kernels.moe_dispatch import ops as moe_ops, ref as moe_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref
from repro.kernels.wordcount_hash import ops as wc_ops, ref as wc_ref


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# wordcount_hash — Map-phase histogram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,vocab,hash_mod", [
    (256, 128, 0), (1024, 512, 0), (4096, 1000, 0),
    (1024, 512, 8), (2048, 300, 16),
])
def test_wordcount_hist_sweep(n, vocab, hash_mod):
    keys = jax.random.randint(jax.random.key(n), (n,), 0, vocab)
    keys = keys.astype(jnp.int32)
    got = wc_ops.wordcount_hist(keys, vocab, hash_mod=hash_mod,
                                interpret=True)
    want = wc_ref.hist_ref(keys, vocab, hash_mod=hash_mod)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_wordcount_hist_with_sentinels():
    from repro.core.kv import KEY_SENTINEL
    keys = jnp.array([1, 2, 1, int(KEY_SENTINEL), 3, int(KEY_SENTINEL)],
                     jnp.int32)
    keys = jnp.pad(keys, (0, 250), constant_values=int(KEY_SENTINEL))
    got = wc_ops.wordcount_hist(keys, 8, interpret=True)
    want = wc_ref.hist_ref(keys, 8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got[1]) == 2 and int(got[2]) == 1 and int(got[3]) == 1


# ---------------------------------------------------------------------------
# flash_attention — prefill/train attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,causal,window,dtype", [
    (2, 256, 4, 4, 64, True, 0, jnp.float32),
    (1, 512, 8, 2, 64, True, 0, jnp.float32),      # GQA 4:1
    (2, 256, 4, 1, 128, True, 0, jnp.float32),     # MQA
    (1, 384, 4, 4, 64, False, 0, jnp.float32),     # bidirectional (encoder)
    (1, 512, 4, 4, 64, True, 128, jnp.float32),    # sliding window
    (2, 256, 4, 4, 64, True, 0, jnp.bfloat16),
    (1, 640, 4, 2, 64, True, 256, jnp.bfloat16),   # SWA + GQA + ragged S
])
def test_flash_attention_sweep(B, S, H, KV, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.key(S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    got = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 block_q=128, block_kv=128, interpret=True)
    want = fa_ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# flash_decode — one-token query vs long KV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KV,hd,t,dtype", [
    (2, 512, 8, 2, 64, 300, jnp.float32),
    (1, 1024, 4, 4, 64, 1023, jnp.float32),
    (4, 256, 8, 1, 128, 17, jnp.float32),          # MQA, short fill
    (2, 512, 8, 2, 64, 300, jnp.bfloat16),
])
def test_flash_decode_sweep(B, S, H, KV, hd, t, dtype):
    ks = jax.random.split(jax.random.key(S + t), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    got = fd_ops.flash_decode(q, k, v, jnp.int32(t), block_kv=128,
                              interpret=True)
    want = fd_ref.flash_decode_ref(q, k, v, jnp.int32(t))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_decode_masks_future_slots():
    """Entries at positions >= t must not contribute."""
    B, S, H, KV, hd = 1, 256, 2, 2, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    t = 64
    out1 = fd_ops.flash_decode(q, k, v, jnp.int32(t), block_kv=64,
                               interpret=True)
    k2 = k.at[:, t:].set(999.0)
    v2 = v.at[:, t:].set(-999.0)
    out2 = fd_ops.flash_decode(q, k2, v2, jnp.int32(t), block_kv=64,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# moe_dispatch — token→expert bucket slots
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,E", [(256, 8), (1024, 16), (512, 64), (333, 7)])
def test_moe_bucket_slots_sweep(T, E):
    eids = jax.random.randint(jax.random.key(T * E), (T,), 0, E)
    eids = eids.astype(jnp.int32)
    got = moe_ops.bucket_slots(eids, E, interpret=True)
    want = moe_ref.bucket_slots_ref(eids, E)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got, want)


# ---------------------------------------------------------------------------
# ssd_scan — Mamba2 chunked state-space duality
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,Ph,N,G,chunk,dtype", [
    (2, 512, 4, 64, 32, 1, 128, jnp.float32),
    (1, 256, 8, 32, 16, 1, 64, jnp.float32),
    (1, 384, 4, 64, 32, 1, 128, jnp.float32),      # ragged S vs chunk
    (2, 256, 4, 64, 16, 1, 128, jnp.bfloat16),
])
def test_ssd_scan_sweep(B, S, H, Ph, N, G, chunk, dtype):
    ks = jax.random.split(jax.random.key(S + N), 5)
    x = jax.random.normal(ks[0], (B, S, H, Ph), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32))
    Bm = jax.random.normal(ks[3], (B, S, G, N), dtype)
    C = jax.random.normal(ks[4], (B, S, G, N), dtype)
    y, st = ssd_ops.ssd(x, dt, A, Bm, C, chunk=chunk, interpret=True)
    yr, str_ = ssd_ref.ssd_ref(x, dt, A, Bm, C)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(st, np.float32),
                               np.asarray(str_, np.float32), **_tol(dtype))


def test_ssd_scan_carries_initial_state():
    """Streaming invariant: scan(x, init=s0) == scan of concatenated halves."""
    B, S, H, Ph, N = 1, 256, 2, 32, 16
    ks = jax.random.split(jax.random.key(9), 5)
    x = jax.random.normal(ks[0], (B, S, H, Ph), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32))
    Bm = jax.random.normal(ks[3], (B, S, 1, N), jnp.float32)
    C = jax.random.normal(ks[4], (B, S, 1, N), jnp.float32)
    y_full, st_full = ssd_ops.ssd(x, dt, A, Bm, C, chunk=64, interpret=True)
    h = S // 2
    y1, st1 = ssd_ops.ssd(x[:, :h], dt[:, :h], A, Bm[:, :h], C[:, :h],
                          chunk=64, interpret=True)
    y2, st2 = ssd_ops.ssd(x[:, h:], dt[:, h:], A, Bm[:, h:], C[:, h:],
                          chunk=64, init_state=st1, interpret=True)
    np.testing.assert_allclose(np.asarray(y_full[:, h:]), np.asarray(y2),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(st_full), np.asarray(st2),
                               atol=2e-3, rtol=2e-3)


# ---------------------------------------------------------------------------
# fused_map — the fused 1S engine step (local reduce -> owner lookup ->
# bucketize -> window fold). Contract: every output bit-identical to the
# pure-jnp composition of the unfused hot-path functions (ref.py), so the
# whole matrix asserts with assert_array_equal — no tolerances.
# ---------------------------------------------------------------------------

from repro.core.kv import KEY_SENTINEL  # noqa: E402
from repro.kernels.fused_map import ops as fm_ops, ref as fm_ref  # noqa: E402


def _fused_case(rng, S, V, P, cap, *, split=False, dupes=False,
                near_sat=False, n_pending=None):
    keys = rng.integers(0, V, S).astype(np.int32)
    if dupes:
        keys[:] = keys[0]                       # every record the same key
    keys[rng.random(S) < 0.15] = KEY_SENTINEL   # padding records
    vals = rng.integers(0, 100, S).astype(np.int32)
    if near_sat:
        from repro.core.combine import SAT_MAX
        vals = (SAT_MAX - rng.integers(0, 4, S)).astype(np.int32)
    omap = rng.integers(0, P, V).astype(np.int32)
    osplit = np.ones((V,), np.int32)
    if split:
        osplit[rng.random(V) < 0.3] = rng.integers(2, P + 1)
    pk = np.full((P, cap), KEY_SENTINEL, np.int32)
    pv = np.zeros((P, cap), np.int32)
    n_pending = cap if n_pending is None else n_pending
    pk[:, :n_pending] = rng.integers(0, V, (P, n_pending))
    pv[:, :n_pending] = rng.integers(0, 50, (P, n_pending))
    table = rng.integers(0, 1000, V).astype(np.int32)
    return tuple(jnp.asarray(a) for a in
                 (keys, vals, omap, osplit, pk, pv, table))


def _assert_fused_matches_ref(args, rep, tid, P, cap, blk):
    keys, vals, omap, osplit, pk, pv, table = args
    rep, tid = jnp.int32(rep), jnp.int32(tid)
    got = fm_ops.fused_map_step(keys, vals, rep, tid, omap, osplit,
                                pk, pv, table, n_procs=P, cap=cap,
                                block_voc=blk, interpret=True)
    want = fm_ref.fused_step_ref(keys, vals, rep, tid, omap, osplit,
                                 pk, pv, table, n_procs=P, cap=cap)
    for name, g, w in zip(("table", "bk", "bv", "counts"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    return got


@pytest.mark.parametrize("S,V,P,cap,rep,blk", [
    (32, 256, 4, 8, 1, 64),
    (64, 512, 8, 16, 1, 128),
    (64, 500, 8, 16, 2, 128),    # vocab not a multiple of the tile
    (128, 64, 4, 8, 3, 64),      # vocab smaller than the tile
    (16, 2048, 2, 4, 1, 512),    # many tiles, tiny task
])
def test_fused_map_sweep(S, V, P, cap, rep, blk):
    rng = np.random.default_rng(S * 31 + V)
    args = _fused_case(rng, S, V, P, cap, split=True)
    _assert_fused_matches_ref(args, rep, 7, P, cap, blk)


def test_fused_map_capacity_one_buckets():
    """cap=1: all but one record per owner overflows into the local fold
    (ownership transfer) — nothing may be dropped."""
    rng = np.random.default_rng(0)
    S, V, P, cap = 48, 128, 4, 1
    args = _fused_case(rng, S, V, P, cap)
    table, bk, bv, counts = _assert_fused_matches_ref(args, 1, 3, P, cap,
                                                      64)
    assert int(jnp.max(counts)) <= cap
    # conservation: window delta + pushed bucket records == input records
    keys, vals, omap, osplit, pk, pv, table_in = args
    from repro.core.kv import local_reduce_repeated
    uk, uv = local_reduce_repeated(keys, vals, S, jnp.int32(1))
    total_in = (fm_ref.records_dense(uk, uv, V)
                + fm_ref.records_dense(pk, pv, V))
    total_out = (np.asarray(table) - np.asarray(table_in)
                 + np.asarray(fm_ref.records_dense(bk, bv, V)))
    np.testing.assert_array_equal(total_out, np.asarray(total_in))


def test_fused_map_all_duplicate_keys():
    """One unique key: the dup-sum collapses the task to a single record
    and one owner takes the whole push."""
    rng = np.random.default_rng(1)
    S, V, P, cap = 32, 100, 3, 4
    args = _fused_case(rng, S, V, P, cap, dupes=True)
    _, bk, _, counts = _assert_fused_matches_ref(args, 2, 5, P, cap, 64)
    live = np.asarray(bk) != int(KEY_SENTINEL)
    assert live.sum() <= 1 and int(np.asarray(counts).sum()) <= 1


def test_fused_map_overflow_saturation_near_sat_max():
    """Values at SAT_MAX: the window fold wraps mod 2^32 exactly like the
    unfused DenseWindow.put (the *saturating* accounting lives downstream
    in the Combine tree, which both paths share unchanged)."""
    rng = np.random.default_rng(2)
    S, V, P, cap = 24, 128, 4, 4
    args = _fused_case(rng, S, V, P, cap, near_sat=True)
    _assert_fused_matches_ref(args, 1, 9, P, cap, 64)


def test_fused_map_split_key_replica_routing():
    """A hot key split over k replicas must route by mixed task id —
    different tasks land on different replica ranks, and each placement
    matches lookup_owner bit-exactly."""
    from repro.core.partition import lookup_owner
    S, V, P, cap = 16, 64, 8, 4
    hot = 7
    keys = np.full((S,), hot, np.int32)
    vals = np.ones((S,), np.int32)
    omap = np.zeros((V,), np.int32)
    osplit = np.ones((V,), np.int32)
    osplit[hot] = 4                       # replicas on ranks {0, 1, 2, 3}
    pk = np.full((P, cap), KEY_SENTINEL, np.int32)
    pv = np.zeros((P, cap), np.int32)
    table = np.zeros((V,), np.int32)
    args = tuple(jnp.asarray(a) for a in
                 (keys, vals, omap, osplit, pk, pv, table))
    seen = set()
    for tid in range(8):
        _, bk, _, _ = _assert_fused_matches_ref(args, 1, tid, P, cap, 64)
        owner = int(lookup_owner(args[2], args[3], jnp.asarray([hot]),
                                 jnp.int32(tid), P)[0])
        rows = np.unique(np.nonzero(np.asarray(bk) != int(KEY_SENTINEL))[0])
        np.testing.assert_array_equal(rows, [owner])
        seen.add(owner)
    assert len(seen) > 1 and seen <= {0, 1, 2, 3}


def test_fused_map_repeat_loop_value_preserving():
    """Footnote-5 imbalance: any rep >= 1 yields the identical step."""
    rng = np.random.default_rng(3)
    S, V, P, cap = 32, 256, 4, 8
    args = _fused_case(rng, S, V, P, cap)
    outs = [_assert_fused_matches_ref(args, rep, 11, P, cap, 64)
            for rep in (1, 2, 5)]
    for later in outs[1:]:
        for g, w in zip(later, outs[0]):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.slow
@pytest.mark.parametrize("source", ["array", "zipf"])
def test_fused_job_matches_unfused_streamed(devices8, source):
    """Job-level exactness: a streamed 8-rank run with stealing on and the
    split partitioner produces record-identical results with and without
    the fused hot path, on both a dense array source and a zipf source."""
    out = devices8(f"""
        import numpy as np
        from repro.core.job import JobConfig, submit
        from repro.core.usecases import WordCount
        from repro.data.source import ZipfSource

        if "{source}" == "array":
            rng = np.random.default_rng(4)
            data = rng.integers(0, 300, 8192).astype(np.int32)
        else:
            data = ZipfSource(8192, vocab=300, a=1.8, seed=6)
        base = dict(task_size=64, push_cap=8, n_procs=8, segment=4,
                    stealing=True, partitioner="sampled+split")
        ru = submit(JobConfig(WordCount(vocab=300), **base),
                    data).result()
        rf = submit(JobConfig(WordCount(vocab=300), fused_map=True,
                              **base), data).result()
        assert ru.records == rf.records, "fused != unfused"
        assert len(rf.records) > 0
        print("OK", len(rf.records))
    """)
    assert "OK" in out


def test_fused_map_refused_on_tpu(monkeypatch):
    """The fused kernel does not lower for the TPU: submit refuses it
    there instead of interpreting it or running the unfused path."""
    from repro.core import job
    from repro.core.usecases import WordCount
    monkeypatch.setattr(job, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="does not run on the TPU"):
        job.submit(job.JobConfig(WordCount(vocab=64), fused_map=True,
                                 n_procs=1, task_size=8),
                   np.zeros((64,), np.int32))


def test_fused_map_rejected_on_backend_without_support():
    from repro.core.job import JobConfig, submit
    from repro.core.usecases import WordCount
    with pytest.raises(ValueError, match="fused"):
        submit(JobConfig(WordCount(vocab=64), backend="2s",
                         fused_map=True, n_procs=1, task_size=8),
               np.zeros((64,), np.int32))
