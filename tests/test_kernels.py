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


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-3, rtol=2e-3)


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
