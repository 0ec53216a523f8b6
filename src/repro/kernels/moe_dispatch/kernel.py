"""Bucket-slot kernel — the paper's Displacement window on TPU.

Given per-record expert/owner ids, every record needs its *slot within its
bucket* (where the one-sided put lands) and each bucket its fill count.
That is a segmented prefix-sum: slot[t] = #{t' < t : id[t'] == id[t]}.

TPU formulation: one-hot the ids against the expert lane (E lanes), cumsum
over the token (sublane) axis inside the block, and carry per-expert
running totals across blocks in VMEM scratch — sequential grid over token
blocks, zero data-dependent addressing. Output slots feed the dispatch
gather; counts are the displacement table peers read.

Grid: (token_blocks,), arbitrary (carry dependency).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _slots_kernel(eid_ref, slot_ref, cnt_ref, carry, *, n_experts: int):
    j = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(j == 0)
    def _init():
        carry[...] = jnp.zeros_like(carry)

    eid = eid_ref[0, :]                                   # (B,)
    Bt = eid.shape[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (Bt, n_experts), 1)
    valid = (eid >= 0) & (eid < n_experts)
    oh = ((eid[:, None] == lanes) & valid[:, None]).astype(jnp.int32)
    prefix = jnp.cumsum(oh, axis=0)                       # inclusive
    slot_mat = carry[0, :][None, :] + prefix - 1          # (B, E)
    picked = jnp.sum(jnp.where(oh == 1, slot_mat, 0), axis=1)
    slot_ref[0, :] = jnp.where(valid, picked, -1)
    carry[0, :] = carry[0, :] + prefix[-1, :]

    @pl.when(j == nb - 1)
    def _fin():
        cnt_ref[0, :] = carry[0, :]


def bucket_slots_pallas(eids: jnp.ndarray, n_experts: int, *,
                        block_tok: int = 1024, interpret: bool = True):
    """eids: (T,) int32 (negative / >=E -> invalid). Returns
    (slots (T,) int32 [-1 for invalid], counts (E,) int32)."""
    T = eids.shape[0]
    block_tok = min(block_tok, max(T, 1))
    nb = -(-T // block_tok)
    pad = nb * block_tok - T
    e = jnp.pad(eids, (0, pad), constant_values=-1).reshape(nb, block_tok)

    kernel = functools.partial(_slots_kernel, n_experts=n_experts)
    slots, counts = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((nb, block_tok), jnp.int32),
                   jax.ShapeDtypeStruct((1, n_experts), jnp.int32)),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, block_tok), lambda j: (j, 0))],
        out_specs=(pl.BlockSpec((1, block_tok), lambda j: (j, 0)),
                   pl.BlockSpec((1, n_experts), lambda j: (0, 0))),
        scratch_shapes=[pltpu.VMEM((1, n_experts), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(e)
    return slots.reshape(-1)[:T], counts[0]
