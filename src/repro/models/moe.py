"""Mixture-of-Experts with the paper's decoupled dispatch as a first-class
feature.

``router(token) -> expert`` is exactly the paper's ``hash(key) -> owner``:
tokens are key-value records, experts their owners, and expert parallelism's
all_to_all is the shuffle. Token routing is *structurally imbalanced* (hot
experts), which is the paper's target regime. Two dispatch schedules:

  "2s"  — bulk-synchronous (baseline): route all local tokens, one big
          all_to_all out, expert GEMMs, one big all_to_all back.
          (MPI_Alltoallv after the Map barrier.)
  "1s"  — decoupled (the paper): tokens stream in ``dispatch_groups`` chunks
          through a software-pipelined scan. Step g pushes group g's buckets
          while the expert GEMM of group g-1 and the return push of g-1 run —
          the explicit double buffer from core/onesided.py. Same bytes,
          overlapped schedule; bucket buffers shrink by G (paper Fig 6).

Both run inside one shard_map over the whole mesh: activations enter
sequence-sharded over "model" (each shard owns T_loc tokens), experts are
sharded over "model" (EP), batch over the data axes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import _init

EP_AXIS = "model"


def init_moe(cfg: ModelConfig, key) -> dict:
    d = cfg.d_model
    ffe = cfg.d_ff_expert or cfg.d_ff
    E = cfg.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 7)
    s_in, s_out = d ** -0.5, ffe ** -0.5
    p = {
        "router": _init(ks[0], (d, E), 0.02, jnp.float32),
        "we_gate": _init(ks[1], (E, d, ffe), s_in, dt),
        "we_in": _init(ks[2], (E, d, ffe), s_in, dt),
        "we_out": _init(ks[3], (E, ffe, d), s_out, dt),
    }
    if cfg.n_shared_experts:
        ffs = ffe * cfg.n_shared_experts
        p["ws_gate"] = _init(ks[4], (d, ffs), s_in, dt)
        p["ws_in"] = _init(ks[5], (d, ffs), s_in, dt)
        p["ws_out"] = _init(ks[6], (ffs, d), s_out, dt)
    return p


# ---------------------------------------------------------------------------
# routing + bucketing (sender side) — the hash->owner of the paper
# ---------------------------------------------------------------------------

def _route(cfg: ModelConfig, router_w, x_flat):
    """x_flat: (T, D) -> (expert_ids (T,k), gates (T,k), probs (T,E))."""
    logits = (x_flat.astype(jnp.float32) @ router_w)
    probs = jax.nn.softmax(logits, -1)
    gates, ids = lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return ids.astype(jnp.int32), gates, probs


def _aux_loss(cfg: ModelConfig, probs, ids, sum_axes=()):
    """Switch-style load-balancing loss.

    ``sum_axes``: mesh axes the tokens are *sharded* over — per-shard counts
    and prob sums psum across them so the sharded loss equals the
    unpartitioned one exactly (not a mean-of-means approximation)."""
    E = cfg.n_experts
    T = probs.shape[0]
    counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    sum_probs = jnp.sum(probs.astype(jnp.float32), 0)
    n_shards = 1
    for ax in sum_axes:
        counts = lax.psum(counts, ax)
        sum_probs = lax.psum(sum_probs, ax)
        n_shards *= lax.axis_size(ax)
    T_tot = T * n_shards
    frac_tokens = counts / max(T_tot * cfg.top_k, 1)
    frac_probs = sum_probs / max(T_tot, 1)
    return E * jnp.sum(frac_tokens * frac_probs)


def _bucket_indices(shard_ids, valid, tp: int, cap: int):
    """Slot each record into (tp, cap) peer buckets (sender side).

    Returns flat gather indices (tp*cap,) into the record axis, -1 = empty.
    Overflow records are dropped (capacity-factor semantics — the MoE
    equivalent of the paper's ownership transfer is the residual connection:
    dropped tokens simply keep their residual value).
    """
    Tk = shard_ids.shape[0]
    sid = jnp.where(valid, shard_ids, tp)
    order = jnp.argsort(sid, stable=True)
    s_sorted = sid[order]
    start = jnp.searchsorted(s_sorted, jnp.arange(tp + 1))
    pos = jnp.arange(Tk) - start[jnp.clip(s_sorted, 0, tp)]
    ok = (pos < cap) & (s_sorted < tp)
    flat = jnp.where(ok, s_sorted * cap + pos, tp * cap)
    idx = jnp.full((tp * cap + 1,), -1, jnp.int32).at[flat].set(
        jnp.where(ok, order, -1).astype(jnp.int32))[:-1]
    return idx                                             # (tp*cap,)


def _gather_records(x, idx):
    """x: (T, D); idx: (M,) with -1 invalid -> (M, D) zeros for invalid."""
    safe = jnp.clip(idx, 0, x.shape[0] - 1)
    out = x[safe]
    return jnp.where((idx >= 0)[:, None], out, 0)


def _expert_gemm(cfg, p, toks, eids, valid):
    """toks: (M, D) received records; eids: (M,) local expert ids.

    Groups records into per-local-expert capacity buffers, runs the SwiGLU
    expert GEMMs batched over E_loc, and scatters results back to the
    record slots.
    """
    M, D = toks.shape
    E_loc = p["we_gate"].shape[0]
    cap_e = -(-M // E_loc)  # ceil — worst case all records on one expert is
    cap_e = min(M, int(cap_e * 4))  # 4x headroom for grouping skew
    eid = jnp.where(valid, eids, E_loc)
    order = jnp.argsort(eid, stable=True)
    es = eid[order]
    start = jnp.searchsorted(es, jnp.arange(E_loc + 1))
    pos = jnp.arange(M) - start[jnp.clip(es, 0, E_loc)]
    ok = (pos < cap_e) & (es < E_loc)
    flat = jnp.where(ok, es * cap_e + pos, E_loc * cap_e)
    slot_of_record = jnp.full((E_loc * cap_e + 1,), -1, jnp.int32).at[
        flat].set(jnp.where(ok, order, -1).astype(jnp.int32))[:-1]
    grouped = _gather_records(toks, slot_of_record)        # (E_loc*cap_e, D)
    grouped = grouped.reshape(E_loc, cap_e, D)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", grouped, p["we_gate"]))
    h = jnp.einsum("ecd,edf->ecf", grouped, p["we_in"])
    out = jnp.einsum("ecf,efd->ecd", g * h, p["we_out"])
    out = out.reshape(E_loc * cap_e, D)
    # scatter back to record slots
    res = jnp.zeros((M + 1, D), toks.dtype).at[
        jnp.where(slot_of_record >= 0, slot_of_record, M)
    ].add(out, mode="drop")[:M]
    return res


# ---------------------------------------------------------------------------
# dispatch schedules
# ---------------------------------------------------------------------------

def _a2a(x, axis):
    """all_to_all that degrades to identity when unpartitioned (axis None)."""
    if axis is None:
        return x
    return lax.all_to_all(x, axis, 0, 0)


def _dispatch_2s(cfg, p, x_flat, ids, gates, tp, E_loc, axis, vma_axes=(),
                 unroll: bool = False):
    """Bulk-synchronous EP dispatch (baseline)."""
    T, D = x_flat.shape
    k = cfg.top_k
    Tk = T * k
    cap = int(cfg.capacity_factor * Tk / tp) + 1
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    tok_of = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    shard = flat_ids // E_loc
    idx = _bucket_indices(shard, jnp.ones((Tk,), bool), tp, cap)
    send_tok = _gather_records(x_flat, jnp.where(idx >= 0, tok_of[
        jnp.clip(idx, 0, Tk - 1)], -1))
    send_eloc = jnp.where(idx >= 0, flat_ids[jnp.clip(idx, 0, Tk - 1)] % E_loc,
                          -1).astype(jnp.int32)
    send_tok = send_tok.reshape(tp, cap, D)
    send_eloc = send_eloc.reshape(tp, cap)
    recv_tok = _a2a(send_tok, axis)
    recv_eloc = _a2a(send_eloc, axis)
    out = _expert_gemm(cfg, p, recv_tok.reshape(-1, D),
                       recv_eloc.reshape(-1), recv_eloc.reshape(-1) >= 0)
    back = _a2a(out.reshape(tp, cap, D), axis)
    back = back.reshape(tp * cap, D)
    # weighted scatter-add into token outputs
    rec = jnp.clip(idx, 0, Tk - 1)
    w = jnp.where(idx >= 0, flat_gates[rec], 0.0)
    tgt = jnp.where(idx >= 0, tok_of[rec], T)
    y = jnp.zeros((T + 1, D), x_flat.dtype).at[tgt].add(
        back * w[:, None].astype(back.dtype), mode="drop")[:T]
    return y


def _dispatch_1s(cfg, p, x_flat, ids, gates, tp, E_loc, axis, vma_axes=(),
                 unroll: bool = False):
    """Decoupled pipelined dispatch — the paper's technique.

    scan step g:   push buckets(g)            [all_to_all, async]
                   GEMM recv(g-1)             [overlaps the push]
                   push-back out(g-1)         [all_to_all, async]
                   scatter back(g-1) into y
    """
    T, D = x_flat.shape
    k = cfg.top_k
    G = max(1, min(cfg.dispatch_groups, T))
    assert T % G == 0, (T, G)
    Tg = T // G
    Tkg = Tg * k
    cap = int(cfg.capacity_factor * Tkg / tp) + 1

    tok_of = jnp.repeat(jnp.arange(Tg, dtype=jnp.int32), k)  # constant

    def bucket_group(g):
        off = g * Tg
        x_g = lax.dynamic_slice_in_dim(x_flat, off, Tg, 0)
        ids_g = lax.dynamic_slice_in_dim(ids, off, Tg, 0).reshape(-1)
        gates_g = lax.dynamic_slice_in_dim(gates, off, Tg, 0).reshape(-1)
        shard = ids_g // E_loc
        idx = _bucket_indices(shard, jnp.ones((Tkg,), bool), tp, cap)
        rec = jnp.clip(idx, 0, Tkg - 1)
        send_tok = _gather_records(x_g, jnp.where(idx >= 0, tok_of[rec], -1))
        send_eloc = jnp.where(idx >= 0, ids_g[rec] % E_loc, -1).astype(
            jnp.int32)
        return (send_tok.reshape(tp, cap, D), send_eloc.reshape(tp, cap),
                idx, gates_g)

    def step(carry, g):
        y, recv_tok, recv_eloc, idx_p, gates_p = carry
        # (1) push group g buckets (skipped past the last group: zero work,
        #     but scan needs uniform structure — we mask with validity)
        send_tok, send_eloc, idx, gates_g = bucket_group(
            jnp.minimum(g, G - 1))
        r_tok = _a2a(send_tok, axis)
        r_eloc = _a2a(send_eloc, axis)
        # (2) expert GEMM of the previous group's received records
        out = _expert_gemm(cfg, p, recv_tok.reshape(-1, D),
                           recv_eloc.reshape(-1), recv_eloc.reshape(-1) >= 0)
        # (3) return push
        back = _a2a(out.reshape(tp, cap, D), axis)
        back = back.reshape(tp * cap, D)
        # (4) weighted scatter into the previous group's slice of y
        g_p = jnp.clip(g - 1, 0, G - 1)   # previous group's base offset
        rec_p = jnp.clip(idx_p, 0, Tkg - 1)
        w = jnp.where(idx_p >= 0, gates_p[rec_p], 0.0)
        tgt = jnp.where(idx_p >= 0, tok_of[rec_p] + g_p * Tg, T)
        y = y.at[tgt].add(back * w[:, None].astype(back.dtype), mode="drop")
        return (y, r_tok, r_eloc, idx, gates_g), None

    y0 = jnp.zeros((T + 1, D), x_flat.dtype)
    z_tok = jnp.zeros((tp, cap, D), x_flat.dtype)
    z_eloc = jnp.full((tp, cap), -1, jnp.int32)
    z_idx = jnp.full((tp * cap,), -1, jnp.int32)
    z_gates = jnp.zeros((Tkg,), jnp.float32)
    carry = (y0, z_tok, z_eloc, z_idx, z_gates)
    if vma_axes and hasattr(lax, "pcast"):
        carry = jax.tree.map(
            lambda a: lax.pcast(a, vma_axes, to="varying"), carry)
    # G pushes + 1 drain step for the in-flight group
    if unroll:
        for g in range(G + 1):     # cost-exact HLO for the dry-run variants
            carry, _ = step(carry, jnp.int32(g))
    else:
        carry, _ = lax.scan(step, carry, jnp.arange(G + 1))
    return carry[0][:T]


def _dispatch_replicated(cfg, p, x_flat, ids, gates, E_loc, axis):
    """Decode-time EP: tokens replicated over the model axis (S=1 cannot be
    sequence-sharded). Every shard runs its local experts on the tokens
    routed to them and the outputs psum over the axis — no all_to_all, the
    right schedule when tokens-per-step is tiny.

    With ``cfg.expert_tp_axis`` (serve sharding, §Perf): each expert's d_ff
    is additionally TP-sharded over that axis; expert outputs are partial
    sums, so the final psum also reduces over it — no weight gather ever."""
    T, D = x_flat.shape
    k = cfg.top_k
    Tk = T * k
    shard = lax.axis_index(axis) if axis is not None else 0
    flat_ids = ids.reshape(-1)
    flat_gates = gates.reshape(-1)
    tok_of = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
    mine = (flat_ids // E_loc) == shard
    toks = x_flat[tok_of]
    out = _expert_gemm(cfg, p, toks, flat_ids % E_loc, mine)
    w = jnp.where(mine, flat_gates, 0.0)
    y = jnp.zeros((T, D), x_flat.dtype).at[tok_of].add(
        out * w[:, None].astype(out.dtype))
    if axis is not None:
        axes = (axis,)
        if cfg.expert_tp_axis:
            axes = axes + (cfg.expert_tp_axis,)
        y = lax.psum(y, axes)
    return y


# ---------------------------------------------------------------------------
# the MoE layer
# ---------------------------------------------------------------------------

def moe_forward(cfg: ModelConfig, p: dict, x, *, mesh=None, dp_entry=None,
                unroll: bool = False):
    """x: (B, S, D). Returns (y, aux_loss). When ``mesh`` is None the layer
    runs unpartitioned (smoke tests); otherwise inside a mesh-wide shard_map
    with tokens sequence-sharded over "model" and experts EP-sharded. When S
    is not divisible by tp (decode: S=1), tokens replicate over "model" and
    the replicated dispatch runs instead. ``unroll`` unrolls the 1s dispatch
    scan (cost-exact HLO for the dry-run roofline variants)."""
    B, S, D = x.shape
    tp_size = 1
    if mesh is not None:
        tp_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get(
            EP_AXIS, 1)
    seq_shardable = S % max(tp_size, 1) == 0

    def body(x_blk, *expert_leaves):
        p_blk = dict(zip(expert_keys, expert_leaves))
        p_blk["router"] = p["router"]
        tp = lax.axis_size(EP_AXIS) if mesh is not None else 1
        axis = EP_AXIS if mesh is not None else None
        vma = tuple(mesh.axis_names) if mesh is not None else ()
        E_loc = p_blk["we_gate"].shape[0]
        Bl, Sl, _ = x_blk.shape
        x_flat = x_blk.reshape(-1, D)
        T_loc = x_flat.shape[0]
        gathered = (mesh is not None and not seq_shardable
                    and cfg.expert_tp_axis)
        if gathered:
            # serve sharding: every shard sees all tokens so the
            # ffe-partial expert outputs can sum across the TP axis
            x_use = lax.all_gather(x_flat, cfg.expert_tp_axis, axis=0,
                                   tiled=True)
        else:
            x_use = x_flat
        ids, gates, probs = _route(cfg, p_blk["router"], x_use)
        # axes the tokens are actually sharded over: the dp entry (batch)
        # plus the model axis when the sequence is sharded over it
        sum_axes = ()
        if mesh is not None and not gathered:
            dp_axes = (dp_entry if isinstance(dp_entry, tuple)
                       else (dp_entry,) if dp_entry else ())
            sum_axes = tuple(dp_axes) + (
                (EP_AXIS,) if seq_shardable else ())
        aux = _aux_loss(cfg, probs, ids, sum_axes)
        if mesh is not None:
            for ax in mesh.axis_names:          # replicate the scalar
                aux = lax.pmean(aux, ax)
        if mesh is not None and not seq_shardable:
            y = _dispatch_replicated(cfg, p_blk, x_use, ids, gates,
                                     E_loc, axis)
            if gathered:
                i = lax.axis_index(cfg.expert_tp_axis)
                y = lax.dynamic_slice_in_dim(y, i * T_loc, T_loc, 0)
        else:
            fn = _dispatch_1s if cfg.dispatch_mode == "1s" else _dispatch_2s
            y = fn(cfg, p_blk, x_flat, ids, gates, tp, E_loc, axis, vma,
                   unroll=unroll)
        return y.reshape(Bl, Sl, D), aux

    expert_keys = ["we_gate", "we_in", "we_out"]
    if mesh is None:
        y, aux = body(x, *[p[k] for k in expert_keys])
    else:
        seq_entry = EP_AXIS if seq_shardable else None
        et = cfg.expert_tp_axis or None
        w_specs = [P(EP_AXIS, None, et), P(EP_AXIS, None, et),
                   P(EP_AXIS, et, None)]
        y, aux = shard_map(
            body, mesh=mesh,
            in_specs=(P(dp_entry, seq_entry, None), *w_specs),
            out_specs=(P(dp_entry, seq_entry, None), P()),
        )(x, *[p[k] for k in expert_keys])

    # shared experts (dense, TP-sharded like a normal MLP)
    if cfg.n_shared_experts:
        g = jax.nn.silu(x @ p["ws_gate"])
        h = x @ p["ws_in"]
        y = y + (g * h) @ p["ws_out"]
    return y, aux
