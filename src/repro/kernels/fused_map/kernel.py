"""Fused 1S engine step: local-reduce -> owner lookup -> bucketize -> fold.

The unfused hot path (core/onesided.py::_step) materializes the (vocab,)
dense window **twice per task** — once folding the in-flight chunk, once
folding the overflow records — plus three argsort passes (local_reduce and
bucketize). This kernel streams the window through VMEM exactly once per
step and keeps every record-domain intermediate on-chip, which is the
whole win: at engine scale the table traffic dominates, so fusing the two
folds into one pass halves the hot loop's bytes moved (fig12 states this
as achieved fraction of memory bandwidth, not just a relative speedup).

Structure (one sequential grid over vocab tiles, wordcount_hash's
revisited-block idiom rotated into the record domain):

  grid step 0   the record pass: dup-sum the task's records with an
                S x S first-occurrence compare (the compare-reduce idiom
                of kernels/wordcount_hash, applied record-vs-record
                instead of record-vs-vocab — O(S^2), vocab-independent),
                rank unique keys ascending so the layout is bit-identical
                to kv.local_reduce, re-run the whole reduction under the
                footnote-5 repeat loop, look owners up in the carried
                owner_map/owner_split (split keys pick a replica by mixed
                task id, exactly partition.lookup_owner), place records
                into per-owner push buckets with kv.bucketize's capacity
                rule, and stash the overflow in VMEM scratch. The scratch
                persists across the sequential grid (flash_decode's m/l/acc
                pattern), so overflow is *carried*, never re-read from HBM.
  every step j  fold the previous step's pending chunk and the scratch
                overflow into table tile j (on-chip read-modify-write,
                one HBM read + one write per tile).

Exactness contract: every output — folded table, (P, cap) buckets,
per-owner counts — is **bit-identical** to ref.fused_step_ref, i.e. to
the unfused composition, for all int32 inputs (summation order is free
mod 2^32; bucket layout matches because key-ascending rank order equals
local_reduce's sorted layout and bucketize's stable owner sort preserves
it). Overflow records are counted into the window fold, never dropped —
the PR 6 saturating-combine accounting downstream is untouched.

The in-kernel scatters (bucket placement, tile fold) are XLA scatters in
interpret mode; on a real TPU target at these block sizes they lower to
one-hot selects, same as the compare matrices. The record pass is O(S^2),
so the fused path targets moderate task sizes (S <= 1024); the unfused
path stays the default and the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.kv import KEY_SENTINEL, mix32


def _dup_sum(keys, vals, out_cap: int):
    """First-occurrence dup-sum with key-ascending ranks — value-identical
    to kv.local_reduce(keys, vals, out_cap) for n_unique <= out_cap."""
    L = keys.shape[0]
    valid = keys != KEY_SENTINEL
    eq = ((keys[:, None] == keys[None, :])
          & valid[:, None] & valid[None, :])
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    first = valid & (jnp.sum((eq & (jj < ii)).astype(jnp.int32),
                             axis=1) == 0)
    sums = jnp.sum(jnp.where(eq, vals[None, :], 0), axis=1)
    # rank = number of distinct keys strictly smaller -> sorted layout
    less = first[None, :] & (keys[None, :] < keys[:, None])
    rank = jnp.sum(less.astype(jnp.int32), axis=1)
    slot = jnp.where(first, rank, out_cap)          # ghost slot out_cap
    uk = jnp.full((out_cap + 1,), KEY_SENTINEL, jnp.int32).at[slot].set(
        jnp.where(first, keys, KEY_SENTINEL))[:out_cap]
    uv = jnp.zeros((out_cap + 1,), jnp.int32).at[slot].set(
        jnp.where(first, sums, 0))[:out_cap]
    return uk, uv


def _fused_kernel(s_ref, om_ref, os_ref, keys_ref, vals_ref,
                  pk_ref, pv_ref, tin_ref,
                  tout_ref, bk_ref, bv_ref, cnt_ref,
                  ofk_s, ofv_s, *,
                  block_voc: int, n_procs: int, cap: int, vocab: int):
    j = pl.program_id(0)
    P = n_procs

    @pl.when(j == 0)
    def _record_pass():
        keys = keys_ref[...]
        vals = vals_ref[...]
        rep = s_ref[0]
        task_id = s_ref[1]
        S = keys.shape[0]

        # Local reduce + footnote-5 repeat: each extra repetition re-runs
        # the full reduction seeded with a value-preserving dependency on
        # the previous one (kv.local_reduce_repeated's exact recurrence,
        # so even wrap-negative sums replay identically).
        def body(_, carry):
            uk, uv = carry
            k_dep = jnp.where(uv < 0, uk, KEY_SENTINEL)
            v_dep = jnp.where(uv < 0, uv, 0)
            return _dup_sum(jnp.concatenate([keys, k_dep]),
                            jnp.concatenate([vals, v_dep]), S)

        uk, uv = jax.lax.fori_loop(1, jnp.maximum(rep, 1), body,
                                   _dup_sum(keys, vals, S))

        # Owner lookup against the carried partition maps (prefetched
        # once per step, never re-fetched per vocab tile) —
        # partition.lookup_owner verbatim.
        valid_u = (uk != KEY_SENTINEL) & (uk >= 0) & (uk < vocab)
        idx = jnp.where(valid_u, uk, 0)
        base = om_ref[...][idx]
        ksplit = jnp.maximum(os_ref[...][idx], 1)
        pick = (mix32(task_id.astype(jnp.uint32))
                % ksplit.astype(jnp.uint32)).astype(jnp.int32)
        owner = (base + jnp.where(ksplit > 1, pick, 0)) % jnp.int32(P)
        owner = jnp.where(valid_u, owner, jnp.int32(P))

        # Bucketize: slots are already owner-stable in key order, so the
        # position of a record in its owner's bucket is the count of
        # earlier same-owner slots — one more S x S compare-reduce.
        si = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        sj = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        same = (owner[None, :] == owner[:, None]) & (sj < si)
        pos = jnp.sum(same.astype(jnp.int32), axis=1)
        ranks = jax.lax.broadcasted_iota(jnp.int32, (P, S), 0)
        tot = jnp.sum((owner[None, :] == ranks).astype(jnp.int32), axis=1)
        cnt_ref[...] = jnp.minimum(tot, cap)
        in_cap = (pos < cap) & (owner < P)
        flat = jnp.where(in_cap, owner * cap + pos, P * cap)
        bk_ref[...] = jnp.full((P * cap + 1,), KEY_SENTINEL,
                               jnp.int32).at[flat].set(
            jnp.where(in_cap, uk, KEY_SENTINEL))[:-1].reshape(P, cap)
        bv_ref[...] = jnp.zeros((P * cap + 1,), jnp.int32).at[flat].set(
            jnp.where(in_cap, uv, 0))[:-1].reshape(P, cap)
        # overflow -> scratch; folded locally below (ownership transfer)
        of = in_cap | (owner >= P)
        ofk_s[...] = jnp.where(of, KEY_SENTINEL, uk)
        ofv_s[...] = jnp.where(of, 0, uv)

    # Fold the in-flight chunk + overflow into this vocab tile: the one
    # table pass of the fused step (the unfused path makes two).
    base_key = j * block_voc
    tile = tin_ref[...]

    def fold(tile, fk, fv):
        local = fk - base_key
        hit = (fk != KEY_SENTINEL) & (local >= 0) & (local < block_voc)
        return tile.at[jnp.where(hit, local, 0)].add(
            jnp.where(hit, fv, 0))

    tile = fold(tile, pk_ref[...].reshape(-1), pv_ref[...].reshape(-1))
    tile = fold(tile, ofk_s[...], ofv_s[...])
    tout_ref[...] = tile


def fused_map_pallas(keys, vals, rep, task_id, owner_map, owner_split,
                     pending_k, pending_v, table, *, n_procs: int,
                     cap: int, block_voc: int = 0,
                     interpret: bool = True):
    """One fused 1S engine step. keys/vals: (S,) mapped records; rep,
    task_id: int32 scalars; owner_map/owner_split: (vocab,) carried
    partition maps; pending_k/pending_v: (P, cap) in-flight chunk;
    table: (vocab,) dense window. Returns (table, bk, bv, counts),
    bit-identical to ref.fused_step_ref.

    The partition maps ride the scalar-prefetch lane (flash_decode's
    ``t`` / paged-attention's block-table idiom): they are *routing
    tables* consulted by gather, not streamed data, so they must not be
    re-fetched per vocab tile — this is what keeps the fused step's HBM
    traffic at one table pass. ``block_voc=0`` (default) folds the whole
    padded vocab as one tile — right off-TPU and for VMEM-resident
    windows; set a real tile size for larger-than-VMEM windows.
    """
    S = keys.shape[0]
    V = owner_map.shape[0]
    P = n_procs
    block_voc = min(block_voc, V) if block_voc else V
    n_tiles = -(-V // block_voc)
    pad = n_tiles * block_voc - V
    tbl = jnp.pad(table, (0, pad)) if pad else table
    scalars = jnp.stack([jnp.asarray(rep, jnp.int32).reshape(()),
                         jnp.asarray(task_id, jnp.int32).reshape(())])

    kernel = functools.partial(_fused_kernel, block_voc=block_voc,
                               n_procs=P, cap=cap, vocab=V)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # [rep, task_id], owner_map, split
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((S,), lambda j, *s: (0,)),           # keys
            pl.BlockSpec((S,), lambda j, *s: (0,)),           # vals
            pl.BlockSpec((P, cap), lambda j, *s: (0, 0)),     # pending_k
            pl.BlockSpec((P, cap), lambda j, *s: (0, 0)),     # pending_v
            pl.BlockSpec((block_voc,), lambda j, *s: (j,)),   # table tile
        ],
        out_specs=[
            pl.BlockSpec((block_voc,), lambda j, *s: (j,)),   # table tile
            pl.BlockSpec((P, cap), lambda j, *s: (0, 0)),     # bk
            pl.BlockSpec((P, cap), lambda j, *s: (0, 0)),     # bv
            pl.BlockSpec((P,), lambda j, *s: (0,)),           # counts
        ],
        scratch_shapes=[pltpu.VMEM((S,), jnp.int32),          # overflow k
                        pltpu.VMEM((S,), jnp.int32)],         # overflow v
    )
    out_table, bk, bv, counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * block_voc,), jnp.int32),
            jax.ShapeDtypeStruct((P, cap), jnp.int32),
            jax.ShapeDtypeStruct((P, cap), jnp.int32),
            jax.ShapeDtypeStruct((P,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(scalars, owner_map, owner_split, keys, vals,
      pending_k, pending_v, tbl)
    return out_table[:V], bk, bv, counts
