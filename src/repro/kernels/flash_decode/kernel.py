"""Flash-decode: one query token against a long KV cache.

Decode is memory-bound (arithmetic intensity ~2 FLOPs/byte: every cached
key/value byte is read once per step), so the kernel's only job is to
stream the cache through VMEM at full HBM bandwidth while the VPU keeps up.
Per grid step: a (block_kv × hd) K tile + V tile and the per-KV-head query
group (G × hd) — the G query heads of a KV head ride along in one program
so K/V bytes are read once per *group*, not once per head (the GQA
bandwidth saving is the whole point of grouped queries at decode).

Grid: (B*KV, kv_blocks) — kv sequential with (m, l, acc) carry. The current
length ``t`` arrives via scalar prefetch (SMEM) and masks the tail block;
with paging upstream (serve/engine.py) blocks past t are never scheduled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fd_kernel(t_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
               scale: float, block_kv: int):
    ik = pl.program_id(1)
    n_kv = pl.num_programs(1)
    t = t_ref[0]

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    k_start = ik * block_kv

    @pl.when(k_start < t)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale        # (G, hd)
        k = k_ref[0].astype(jnp.float32)                # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (G, bkv)
        kv_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kv_pos < t, s, NEG_INF)
        m_prev = m_s[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(kv_pos < t, p, 0.0)
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


def flash_decode_pallas(q, k, v, t, *, block_kv: int = 1024,
                        interpret: bool = True):
    """q: (BKV, G, hd) query groups; k/v: (BKV, S, hd); t: scalar int32
    current length. Returns (BKV, G, hd)."""
    BKV, G, hd = q.shape
    _, S, _ = k.shape
    block_kv = min(block_kv, S)
    n_kv = -(-S // block_kv)
    pad = n_kv * block_kv - S
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(_fd_kernel, scale=hd ** -0.5,
                               block_kv=block_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BKV, n_kv),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, ik, t_ref: (b, 0, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, ik, t_ref: (b, ik, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, ik, t_ref: (b, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, ik, t_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BKV, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray([t], jnp.int32) if jnp.ndim(t) == 0 else t, q, k, v)
