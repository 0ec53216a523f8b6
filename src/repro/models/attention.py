"""Attention: GQA / MHA / sliding-window / MLA, with train, prefill and
decode paths.

Layout contracts
  activations      (B, S, D)
  q/k/v            (B, S, H|KV, hd)
  GQA/SWA cache    {"k","v"}: (B, S_max, KV, hd)   — seq-sharded over "model"
  SWA cache        ring buffer, S_max = window      — replicated (small)
  MLA cache        {"ckv"}: (B, S_max, lora+rope)   — seq-sharded over "model"

Decode uses a flash-decode scheme: every model shard computes online-softmax
partials over its *sequence slice* of the cache for all heads, then the
partials combine with a max-stabilized psum. This is the uniform layout that
fits 32k–512k caches for every kv_heads count (DESIGN.md §7).

The chunked reference attention here doubles as the Pallas flash kernel's
oracle (kernels/flash_attention/ref.py re-exports it).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from repro.config import ModelConfig
from repro.models.layers import _init, apply_rope, rms_over

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, cross: bool = False) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    p = {
        "wq": _init(ks[0], (d, H * hd), s, dt),
        "wk": _init(ks[1], (d, KV * hd), s, dt),
        "wv": _init(ks[2], (d, KV * hd), s, dt),
        "wo": _init(ks[3], (H * hd, d), (H * hd) ** -0.5, dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dt)
        p["bk"] = jnp.zeros((KV * hd,), dt)
        p["bv"] = jnp.zeros((KV * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def init_mla(cfg: ModelConfig, key) -> dict:
    d, H = cfg.d_model, cfg.n_heads
    nope, rope_d, v_d = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": _init(ks[0], (d, H * (nope + rope_d)), s, dt),
        "w_kv_a": _init(ks[1], (d, lora + rope_d), s, dt),
        "w_kv_b": _init(ks[2], (lora, H * (nope + v_d)), lora ** -0.5, dt),
        "wo": _init(ks[3], (H * v_d, d), (H * v_d) ** -0.5, dt),
        "kv_norm": jnp.ones((lora,), dt),
    }


# ---------------------------------------------------------------------------
# chunked reference attention (flash oracle)
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        q_chunk: int = 512, kv_chunk: int = 512,
                        q_offset: int = 0):
    """Online-softmax chunked attention.

    q: (B, Sq, H, hd); k/v: (B, Skv, KV, hd) with H % KV == 0.
    ``window > 0``: sliding-window (banded) — only the KV band that can be
    seen by each q chunk is touched, so cost is O(Sq * window).
    ``q_offset``: absolute position of q[0] (cross-chunk prefill).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq = math.ceil(Sq / q_chunk)
    scale = hd ** -0.5

    # pad both sequence axes to chunk multiples; padded kv is masked via
    # ``kv_pos < Skv``, padded q rows are sliced off at the end
    Sq_pad = nq * q_chunk
    Skv_pad = math.ceil(Skv / kv_chunk) * kv_chunk
    if Sq_pad != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sq_pad - Sq), (0, 0), (0, 0)))
    if Skv_pad != Skv:
        k = jnp.pad(k, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skv_pad - Skv), (0, 0), (0, 0)))

    qg = q.reshape(B, Sq_pad, KV, G, hd)

    if window > 0:
        band = int(min(Skv_pad,
                       (math.ceil((window + q_chunk) / kv_chunk) + 1)
                       * kv_chunk))
    else:
        band = Skv_pad
    nkv = band // kv_chunk

    def q_step(i):
        q_i = lax.dynamic_slice_in_dim(qg, i * q_chunk, q_chunk, 1)
        q_i = (q_i * scale).astype(q.dtype)
        q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
        if window > 0:
            start = jnp.clip(q_offset + (i + 1) * q_chunk - band, 0,
                             Skv_pad - band)
        else:
            start = 0
        k_b = lax.dynamic_slice_in_dim(k, start, band, 1)
        v_b = lax.dynamic_slice_in_dim(v, start, band, 1)

        def kv_step(carry, j):
            m, l, acc = carry
            k_j = lax.dynamic_slice_in_dim(k_b, j * kv_chunk, kv_chunk, 1)
            v_j = lax.dynamic_slice_in_dim(v_b, j * kv_chunk, kv_chunk, 1)
            kv_pos = start + j * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgh,bckh->bkgqc", q_i, k_j,
                           preferred_element_type=jnp.float32)
            mask = jnp.broadcast_to(kv_pos[None, :] < Skv,
                                    (q_chunk, kv_chunk))
            if causal:
                mask &= kv_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= kv_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, -1))
            p = jnp.exp(s - m_new[..., None])
            # rows fully masked so far have m_new == NEG_INF and would get
            # p = exp(0) = 1 on masked entries — zero them explicitly
            p = jnp.where(mask[None, None, None], p, 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, -1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bckh->bkgqh", p.astype(v.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), jnp.arange(nkv))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, KV, G, q_chunk, hd) -> (B, q_chunk, H, hd)
        return jnp.moveaxis(o, 3, 1).reshape(B, q_chunk, H, hd).astype(q.dtype)

    outs = lax.map(q_step, jnp.arange(nq))            # (nq, B, qc, H, hd)
    return jnp.moveaxis(outs, 0, 1).reshape(B, nq * q_chunk, H, hd)[:, :Sq]


def flash_attention_costexact(q, k, v, *, causal: bool = True,
                              window: int = 0, n_q_chunks: int = 8,
                              q_offset: int = 0):
    """Unrolled, tile-skipping attention — the dry-run cost instrument.

    Python-loops over q chunks (so HLO carries every tile and
    ``cost_analysis`` counts them all — scans are counted once, see
    DESIGN.md §9) and slabs the kv range each q chunk can actually see
    (causal triangle / SWA band), mirroring the Pallas kernel's pl.when
    tile skipping. FLOPs in the lowered HLO == FLOPs the TPU kernel
    executes, at chunk granularity.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    c = max(128, -(-Sq // n_q_chunks))
    nq = -(-Sq // c)
    scale = hd ** -0.5
    qg = q.reshape(B, Sq, KV, G, hd)
    outs = []
    for i in range(nq):
        lo_q = i * c
        hi_q = min(Sq, lo_q + c)
        cq = hi_q - lo_q
        q_i = (qg[:, lo_q:hi_q] * scale).astype(q.dtype)
        abs_hi = q_offset + hi_q
        hi_kv = min(Skv, abs_hi) if causal else Skv
        lo_kv = max(0, q_offset + lo_q - window + 1) if window > 0 else 0
        k_s = k[:, lo_kv:hi_kv]
        v_s = v[:, lo_kv:hi_kv]
        s = jnp.einsum("bqkgh,bckh->bkgqc", q_i, k_s,
                       preferred_element_type=jnp.float32)
        q_pos = q_offset + lo_q + jnp.arange(cq)
        kv_pos = lo_kv + jnp.arange(hi_kv - lo_kv)
        mask = jnp.ones((cq, hi_kv - lo_kv), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(v.dtype), v_s,
                       preferred_element_type=jnp.float32)
        outs.append(jnp.moveaxis(o, 3, 1).reshape(B, cq, H, hd)
                    .astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def attention_dense_ref(q, k, v, *, causal=True, window=0, q_offset=0):
    """O(S^2)-memory oracle for tests."""
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgh,bckh->bkgqc", qg, k).astype(jnp.float32)
    s *= hd ** -0.5
    q_pos = q_offset + jnp.arange(Sq)
    kv_pos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kv_pos[None] <= q_pos[:, None]
    if window > 0:
        mask &= kv_pos[None] > q_pos[:, None] - window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(v.dtype), v)
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# flash-decode core (seq-sharded cache)
# ---------------------------------------------------------------------------

def _decode_partials(q, k, v, kv_pos, t):
    """Per-shard online softmax over a cache slice.

    q: (B, H, hd); k/v: (B, S_loc, KV, hd); kv_pos: (S_loc,) absolute
    positions; t: current length (positions >= t are invalid).
    Returns (o_partial, l, m) for max-stabilized combining.
    """
    B, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd) * (hd ** -0.5)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k,
                   preferred_element_type=jnp.float32)
    valid = ((kv_pos >= 0) & (kv_pos < t))[None, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, -1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid, p, 0.0)
    l = jnp.sum(p, -1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, l, m


def combine_partials(o, l, m, axis: str | None):
    """Combine (o, l, m) partials across ``axis`` (None -> single shard)."""
    if axis is None:
        return (o / jnp.maximum(l, 1e-30)[..., None])
    m_glob = lax.pmax(m, axis)
    corr = jnp.exp(m - m_glob)
    l_glob = lax.psum(l * corr, axis)
    o_glob = lax.psum(o * corr[..., None], axis)
    return o_glob / jnp.maximum(l_glob, 1e-30)[..., None]


def decode_attention_sharded(q, cache_k, cache_v, t, *, mesh, dp_entry,
                             seq_axis: str = "model"):
    """Flash-decode with the cache sequence-sharded over ``seq_axis``.

    q: (B, H, hd) replicated over model; cache: (B, S_max, KV, hd) sharded
    P(dp, model). New k/v must already be written (see update_cache_sharded).
    """
    from jax.sharding import PartitionSpec as P

    B, H, hd = q.shape
    S_max = cache_k.shape[1]

    def inner(q_b, k_b, v_b, t_b):
        S_loc = k_b.shape[1]
        idx = lax.axis_index(seq_axis)
        kv_pos = idx * S_loc + jnp.arange(S_loc)
        o, l, m = _decode_partials(q_b, k_b, v_b, kv_pos, t_b)
        o = combine_partials(o, l, m, seq_axis)
        B_, KV, G, _ = o.shape
        return o.reshape(B_, KV * G, hd).astype(q.dtype)

    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(dp_entry, None, None), P(dp_entry, seq_axis, None, None),
                  P(dp_entry, seq_axis, None, None), P()),
        out_specs=P(dp_entry, None, None),
    )(q, cache_k, cache_v, t)


def update_cache_sharded(cache, new, t, *, mesh, dp_entry,
                         seq_axis: str = "model"):
    """Write one token's k/v (B, KV, hd) at absolute position t into a
    seq-sharded cache (B, S_max, KV, hd). Only the owning shard writes."""
    from jax.sharding import PartitionSpec as P

    def inner(c, n, t_b):
        S_loc = c.shape[1]
        idx = lax.axis_index(seq_axis)
        local = t_b - idx * S_loc
        in_range = (local >= 0) & (local < S_loc)
        pos = jnp.clip(local, 0, S_loc - 1)
        updated = lax.dynamic_update_slice_in_dim(c, n[:, None], pos, 1)
        return jnp.where(in_range, updated, c)

    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(dp_entry, seq_axis, None, None),
                  P(dp_entry, None, None), P()),
        out_specs=P(dp_entry, seq_axis, None, None),
    )(cache, new, t)


# ---------------------------------------------------------------------------
# full attention layer (projections + modes)
# ---------------------------------------------------------------------------

def _qkv(cfg: ModelConfig, p: dict, x, kv_x=None):
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    kv_x = x if kv_x is None else kv_x
    Skv = kv_x.shape[1]
    q = x @ p["wq"]
    k = kv_x @ p["wk"]
    v = kv_x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, Skv, KV, hd)
    v = v.reshape(B, Skv, KV, hd)
    if "q_norm" in p:
        q = rms_over(q, p["q_norm"])
        k = rms_over(k, p["k_norm"])
    return q, k, v


def attention_forward(cfg: ModelConfig, p: dict, x, positions, *,
                      causal=True, use_pallas=False, unroll=False):
    """Train / prefill pass. Returns (out, (k, v)) — k/v feed the cache."""
    q, k, v = _qkv(cfg, p, x)
    q = _rope_bshd(q, positions, cfg.rope_theta)
    k = _rope_bshd(k, positions, cfg.rope_theta)
    window = cfg.sliding_window if cfg.attn_type == "swa" else 0
    if use_pallas:
        from repro.kernels.flash_attention import ops as fa_ops
        o = fa_ops.flash_attention(q, k, v, causal=causal, window=window)
    elif unroll:
        o = flash_attention_costexact(q, k, v, causal=causal, window=window)
    else:
        o = flash_attention_ref(q, k, v, causal=causal, window=window)
    B, S, H, hd = q.shape
    return o.reshape(B, S, H * hd) @ p["wo"], (k, v)


def _rope_bshd(x, positions, theta):
    """RoPE on (B, S, N, hd) with positions (B, S)."""
    xt = x.swapaxes(1, 2)                    # (B, N, S, hd)
    xt = apply_rope(xt, positions[:, None, :], theta)
    return xt.swapaxes(1, 2)


def attention_decode(cfg: ModelConfig, p: dict, x, cache: dict, t, *,
                     mesh=None, dp_entry=None):
    """One-token decode. x: (B, 1, D); cache {"k","v"}: (B, S_max, KV, hd)."""
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q, k, v = _qkv(cfg, p, x)
    pos = jnp.full((B, 1), t, jnp.int32)
    q = _rope_bshd(q, pos, cfg.rope_theta)
    k = _rope_bshd(k, pos, cfg.rope_theta)
    q1, k1, v1 = q[:, 0], k[:, 0], v[:, 0]

    if cfg.attn_type == "swa":
        # ring-buffer cache of size window — replicated (small)
        W = cache["k"].shape[1]
        slot = t % W
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k, slot, 1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v, slot, 1)
        kv_pos = t - ((slot - jnp.arange(W)) % W)     # absolute position/slot
        o, l, m = _decode_partials(q1, ck, cv, kv_pos, t + 1)
        o = combine_partials(o, l, m, None)
        o = o.reshape(B, H, hd)
        new_cache = {"k": ck, "v": cv}
    elif mesh is not None:
        ck = update_cache_sharded(cache["k"], k1, t, mesh=mesh,
                                  dp_entry=dp_entry)
        cv = update_cache_sharded(cache["v"], v1, t, mesh=mesh,
                                  dp_entry=dp_entry)
        o = decode_attention_sharded(q1, ck, cv, t + 1, mesh=mesh,
                                     dp_entry=dp_entry)
        new_cache = {"k": ck, "v": cv}
    else:
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k, t, 1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v, t, 1)
        kv_pos = jnp.arange(ck.shape[1])
        o, l, m = _decode_partials(q1, ck, cv, kv_pos, t + 1)
        o = combine_partials(o, l, m, None).reshape(B, H, hd)
        new_cache = {"k": ck, "v": cv}

    o = o.reshape(B, 1, H * hd).astype(x.dtype)
    return o @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek) — prefill materializes k/v; decode runs absorbed over the
# compressed cache.
# ---------------------------------------------------------------------------

def _mla_expand(cfg, p, ckv):
    """ckv: (B, S, lora) -> k_nope, v: (B, S, H, nope|v)."""
    B, S, _ = ckv.shape
    H, nope, v_d = cfg.n_heads, cfg.qk_nope_dim, cfg.v_head_dim
    kv = ckv @ p["w_kv_b"]
    kv = kv.reshape(B, S, H, nope + v_d)
    return kv[..., :nope], kv[..., nope:]


def mla_forward(cfg: ModelConfig, p: dict, x, positions, *, use_pallas=False,
                unroll=False):
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d, v_d = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = _rope_bshd(q_rope, positions, cfg.rope_theta)
    a = x @ p["w_kv_a"]                                 # (B,S,lora+rope)
    ckv = rms_over(a[..., :cfg.kv_lora_rank], p["kv_norm"])
    k_rope = _rope_bshd(a[..., None, cfg.kv_lora_rank:], positions,
                        cfg.rope_theta)                 # (B,S,1,rope)
    k_nope, v = _mla_expand(cfg, p, ckv)
    q_full = jnp.concatenate([q_nope, q_rope], -1)
    k_full = jnp.concatenate([k_nope,
                              jnp.broadcast_to(k_rope,
                                               k_nope.shape[:-1] + (rope_d,))],
                             -1)
    # pad v to the qk head dim so the shared flash path applies, then slice
    fa = flash_attention_costexact if unroll else flash_attention_ref
    o = fa(q_full, k_full,
           jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                       (0, nope + rope_d - v_d))),
           causal=True)[..., :v_d]
    o = o.reshape(B, S, H * v_d)
    cache = {"ckv": jnp.concatenate([ckv, k_rope[:, :, 0]], -1)}
    return o @ p["wo"], cache


def mla_decode(cfg: ModelConfig, p: dict, x, cache: dict, t, *,
               mesh=None, dp_entry=None):
    """Absorbed MLA decode over the compressed cache (B, S_max, lora+rope)."""
    from jax.sharding import PartitionSpec as P
    B = x.shape[0]
    H = cfg.n_heads
    nope, rope_d, v_d = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    pos = jnp.full((B, 1), t, jnp.int32)
    q = (x @ p["wq"]).reshape(B, 1, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = _rope_bshd(q_rope, pos, cfg.rope_theta)[:, 0]     # (B,H,rope)
    a = (x @ p["w_kv_a"])[:, 0]                                # (B,lora+rope)
    ckv_new = rms_over(a[..., :lora], p["kv_norm"])
    kr_new = apply_rope(a[:, None, lora:], pos, cfg.rope_theta)[:, 0]
    entry = jnp.concatenate([ckv_new, kr_new], -1)             # (B,lora+rope)

    # absorb W_kv_b's key half into q:  q_lora = q_nope @ W_b_k^T per head
    w_b = p["w_kv_b"].reshape(lora, H, nope + v_d)
    w_b_k = w_b[..., :nope]                                    # (lora,H,nope)
    w_b_v = w_b[..., nope:]                                    # (lora,H,v)
    q_lora = jnp.einsum("bhn,lhn->bhl", q_nope[:, 0], w_b_k)   # (B,H,lora)
    qq = jnp.concatenate([q_lora, q_rope], -1)                 # (B,H,lora+rope)

    def inner(qq_b, cache_b, entry_b, t_b):
        S_loc = cache_b.shape[1]
        if mesh is not None:
            idx = lax.axis_index("model")
        else:
            idx = 0
        local = t_b - idx * S_loc
        in_range = (local >= 0) & (local < S_loc)
        posi = jnp.clip(local, 0, S_loc - 1)
        upd = lax.dynamic_update_slice_in_dim(cache_b, entry_b[:, None],
                                              posi, 1)
        cache_b = jnp.where(in_range, upd, cache_b)
        kv_pos = idx * S_loc + jnp.arange(S_loc)
        s = jnp.einsum("bhl,bsl->bhs", qq_b, cache_b,
                       preferred_element_type=jnp.float32)
        s *= (nope + rope_d) ** -0.5
        valid = (kv_pos < t_b + 1)[None, None]
        s = jnp.where(valid, s, NEG_INF)
        m = jnp.max(s, -1)
        pr = jnp.where(valid, jnp.exp(s - m[..., None]), 0.0)
        l = jnp.sum(pr, -1)
        o_l = jnp.einsum("bhs,bsl->bhl", pr.astype(cache_b.dtype),
                         cache_b[..., :lora],
                         preferred_element_type=jnp.float32)
        if mesh is not None:
            m_g = lax.pmax(m, "model")
            corr = jnp.exp(m - m_g)
            l_g = lax.psum(l * corr, "model")
            o_l = lax.psum(o_l * corr[..., None], "model")
        else:
            l_g = l
        o_l = o_l / jnp.maximum(l_g, 1e-30)[..., None]
        return o_l.astype(x.dtype), cache_b

    if mesh is not None:
        o_l, new_cache = shard_map(
            inner, mesh=mesh,
            in_specs=(P(dp_entry, None, None),
                      P(dp_entry, "model", None), P(dp_entry, None), P()),
            out_specs=(P(dp_entry, None, None), P(dp_entry, "model", None)),
        )(qq, cache["ckv"], entry, t)
    else:
        o_l, new_cache = inner(qq, cache["ckv"], entry, t)
    # un-absorb the value half:  o = o_lora @ W_b_v per head
    o = jnp.einsum("bhl,lhv->bhv", o_l.astype(jnp.float32),
                   w_b_v.astype(jnp.float32))
    o = o.reshape(B, 1, H * v_d).astype(x.dtype)
    return o @ p["wo"], {"ckv": new_cache}
