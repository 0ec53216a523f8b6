"""Histogram kernel — the Map + Local-Reduce inner loop on TPU.

The paper's Map phase hashes every word and scatters a <key,1> record into
the owner's bucket. Scatters are hostile to the TPU vector unit, so the
TPU-native formulation is a *tiled compare-reduce histogram*: for a tile of
``block_voc`` key slots and a block of ``block_tok`` tokens, the count is a
(tokens × slots) equality matrix reduced over tokens — pure VPU work with
perfect lane utilization, no data-dependent addressing. (This is the
hardware adaptation DESIGN.md §2 records: hash-scatter → compare-reduce.)

Grid: (vocab_tiles, token_blocks); vocab tiles are parallel, token blocks
sequential (accumulate into the same output tile).

An optional Murmur3-style ownership hash (``hash_mod > 0``) runs *inside*
the kernel so the owner histogram (the paper's Displacement-window math)
costs no extra memory pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


SENTINEL = jnp.iinfo(jnp.int32).max


def _mix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _hist_kernel(tok_ref, out_ref, *, block_voc: int, hash_mod: int):
    i = pl.program_id(0)          # vocab tile
    j = pl.program_id(1)          # token block (sequential)
    toks = tok_ref[0, :]          # (block_tok,)
    valid = toks != SENTINEL
    if hash_mod > 0:
        keys = (_mix32(toks) % jnp.uint32(hash_mod)).astype(jnp.int32)
    else:
        keys = toks
    base = i * block_voc
    ids = base + jax.lax.broadcasted_iota(
        jnp.int32, (toks.shape[0], block_voc), 1)
    hits = (keys[:, None] == ids) & valid[:, None]
    partial = jnp.sum(hits.astype(jnp.int32), axis=0)    # (block_voc,)

    @pl.when(j == 0)
    def _init():
        out_ref[0, :] = partial

    @pl.when(j != 0)
    def _acc():
        out_ref[0, :] = out_ref[0, :] + partial


def hist_pallas(tokens: jnp.ndarray, vocab: int, *, hash_mod: int = 0,
                block_tok: int = 1024, block_voc: int = 512,
                interpret: bool = True) -> jnp.ndarray:
    """tokens: (N,) int32 (SENTINEL = skip). Returns (vocab,) int32 counts
    of ``token`` (hash_mod=0) or ``mix32(token) % hash_mod`` (owner mode —
    then ``vocab`` must be >= hash_mod)."""
    N = tokens.shape[0]
    block_tok = min(block_tok, max(N, 1))
    n_blocks = -(-N // block_tok)
    pad = n_blocks * block_tok - N
    toks = jnp.pad(tokens, (0, pad), constant_values=SENTINEL)
    toks = toks.reshape(n_blocks, block_tok)

    block_voc = min(block_voc, vocab)
    n_tiles = -(-vocab // block_voc)
    vpad = n_tiles * block_voc

    kernel = functools.partial(_hist_kernel, block_voc=block_voc,
                               hash_mod=hash_mod)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_tiles, block_voc), jnp.int32),
        grid=(n_tiles, n_blocks),
        in_specs=[pl.BlockSpec((1, block_tok), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((1, block_voc), lambda i, j: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(toks)
    return out.reshape(vpad)[:vocab]
