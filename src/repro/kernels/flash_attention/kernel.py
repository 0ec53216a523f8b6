"""Blocked online-softmax attention (flash) for prefill/train.

VMEM tiling: per grid step the kernel holds one (block_q × hd) query tile,
one (block_kv × hd) key/value tile and fp32 running (m, l, acc) scratch —
with block_q = block_kv = 512 and hd = 128 that is ~1.4 MB, well inside the
~16 MB v5e VMEM even double-buffered. Matmul dims are multiples of 128 so
the MXU runs dense. GQA never materializes repeated KV heads: the k/v
BlockSpec index-maps H query-head programs onto their KV head
(``bh // group``), so KV reads are shared.

Grid: (B*H, q_blocks, kv_blocks) — kv innermost, sequential (running
softmax carry); q and batch-head parallel. Causal + sliding-window masks
applied per tile; fully-masked tiles are skipped with pl.when (upper
triangle costs nothing, the SWA band skips both sides).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
               scale: float, causal: bool, window: int,
               block_q: int, block_kv: int, s_valid: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_start = iq * block_q
    k_start = ik * block_kv

    # tile-level visibility: any (q, k) pair in this tile unmasked?
    vis = True
    if causal:
        vis = (k_start <= q_start + block_q - 1)
    if window > 0:
        # SWA band: k > q - window  for some pair in tile
        vis = vis & (k_start + block_kv - 1 > q_start - window)

    @pl.when(vis)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, hd)
        k = k_ref[0].astype(jnp.float32)               # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bkv)
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = k_pos < s_valid                          # padded tail
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_s[:, 0] = l_s[:, 0] * corr + jnp.sum(p, axis=1)
        acc_s[...] = acc_s[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:, 0] = m_new

    @pl.when(ik == n_kv - 1)
    def _fin():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[:, 0], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 512, block_kv: int = 512,
                           interpret: bool = True):
    """q: (BH, Sq, hd); k/v: (BKV, Skv, hd); H = G * KV with BH = B*H,
    BKV = B*KV — caller lays out batch-major so ``bh // group`` finds the
    KV row. Returns (BH, Sq, hd)."""
    BH, Sq, hd = q.shape
    BKV, Skv, _ = k.shape
    group = BH // BKV
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    n_q = -(-Sq // block_q)
    n_kv = -(-Skv // block_kv)
    q_pad = n_q * block_q - Sq
    kv_pad = n_kv * block_kv - Skv
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, q_pad), (0, 0)))
    if kv_pad:
        k = jnp.pad(k, ((0, 0), (0, kv_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kv_pad), (0, 0)))

    kernel = functools.partial(
        _fa_kernel, scale=hd ** -0.5, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, s_valid=Skv)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((BH, n_q * block_q, hd), q.dtype),
        grid=(BH, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_kv, hd),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
            pl.BlockSpec((1, block_kv, hd),
                         lambda bh, iq, ik, g=group: (bh // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, iq, ik: (bh, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
