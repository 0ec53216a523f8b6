"""Pipeline parallelism across pods (GPipe schedule).

Why pods: the multi-pod mesh's ``pod`` axis is the thin link (DCN, not
ICI). Baseline multi-pod training runs pure DP across pods — a cross-pod
gradient all-reduce of every parameter each step. Pipelining the *layers*
across pods instead turns cross-pod traffic into per-microbatch activation
sends (collective-permute, point-to-point — the cheapest possible pattern
on DCN), which is the paper's decoupled-push principle applied at the pod
level: partial results (activations) stream forward as they are produced
rather than a bulk synchronous exchange at the end.

Mechanics: ``shard_map`` manual over ``pod`` only (data/model stay GSPMD-
automatic inside). Stage s owns ``blocks[s*nb_loc:(s+1)*nb_loc]`` (the
stacked scan-block dim is sharded over ``pod`` — optimizer state shards
with it for free). The GPipe wavefront runs M + S - 1 steps; step t moves
microbatch m = t - s through stage s, with a ``ppermute`` handing
activations to s+1. Invalid (bubble) slots compute masked work — the
standard GPipe bubble, fraction (S-1)/(M+S-1). Loss is computed on the
last stage and psum'd; ``jax.grad`` differentiates through the schedule
(ppermute transposes to the reverse permute).

Scope: dense stacks (MoE layers use a full-mesh shard_map dispatch that
does not nest inside a partial-manual region; PP+EP composition is future
work — recorded in DESIGN.md).
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.models.layers import apply_norm, cross_entropy, embed_tokens, \
    unembed
from repro.models.transformer import _superblock_forward


def _stage_fwd(cfg: ModelConfig, blocks_loc, x, positions, *, remat):
    """Run this stage's nb_loc scanned super-blocks on x."""
    def body(h, bp):
        h, _, _ = _superblock_forward(cfg, bp, h, positions, 0, causal=True)
        return h, None

    if remat != "none":
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, blocks_loc)
    return x


def gpipe_loss_fn(cfg: ModelConfig, params: dict, batch: dict, *, mesh,
                  n_microbatches: int, stage_axis: str = "pod",
                  remat: str = "full"):
    """Pipeline-parallel loss over the ``stage_axis``.

    params["blocks"] leaves arrive stage-sharded (leading dim over
    ``stage_axis``); everything else replicated over it. batch: full
    global batch; microbatched internally (M = n_microbatches).
    """
    M = n_microbatches
    tokens, labels = batch["tokens"], batch["labels"]
    B, S = tokens.shape
    assert B % M == 0, (B, M)
    mb = B // M
    tok_mb = tokens.reshape(M, mb, S)
    lab_mb = labels.reshape(M, mb, S)

    # tok_mb/lab_mb enter as explicit shard_map operands (not closure
    # captures): jax 0.4.x shard_map cannot infer specs for captured
    # tracers when the region is transposed for the backward pass
    def staged(blocks_loc, embed_p, head_p, tok_mb, lab_mb):
        n_stages = lax.axis_size(stage_axis)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32),
                                     (mb, S))
        fwd = partial(_stage_fwd, cfg, blocks_loc, positions=positions,
                      remat=remat)
        # send stage s -> s+1 (last stage's send is dropped)
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def step(carry, t):
            # axis_index is taken per-step on purpose: as a loop-invariant
            # scalar it would become a rank-0 shard_map residual, which
            # jax 0.4.x partial-eval mislabels (see note at the call site)
            sid = lax.axis_index(stage_axis)
            x_in, loss_sum, tok_sum = carry
            m = t - sid                          # microbatch at this stage
            valid = (m >= 0) & (m < M)
            m_c = jnp.clip(m, 0, M - 1)
            # stage 0 ingests a fresh microbatch; others take the handoff
            x0 = embed_tokens(cfg, embed_p, tok_mb[m_c])
            x = jnp.where(sid == 0, x0, x_in).astype(x0.dtype)
            y = fwd(x)
            # last stage: head + CE on its finished microbatch
            h = apply_norm(cfg, head_p["final_norm"], y)
            logits = unembed(cfg, head_p, h)
            ce = cross_entropy(logits, lab_mb[m_c])
            use = valid & (sid == n_stages - 1)
            loss_sum = loss_sum + jnp.where(use, ce, 0.0)
            tok_sum = tok_sum + jnp.where(use, 1.0, 0.0)
            # hand off to the next stage (ppermute; transposed in backward)
            y_send = jnp.where(valid, y, 0.0).astype(y.dtype)
            x_next = lax.ppermute(y_send, stage_axis, perm)
            return (x_next, loss_sum, tok_sum), None

        zero_x = jnp.zeros((mb, S, cfg.d_model), jnp.dtype(cfg.dtype))
        carry = (zero_x, jnp.float32(0.0), jnp.float32(0.0))
        (x, loss_sum, tok_sum), _ = lax.scan(
            step, carry, jnp.arange(M + n_stages - 1))
        # only the last stage holds the loss — share it. The division by
        # the token count happens OUTSIDE the shard_map: as an internal
        # op it would make tok_sum a rank-0 residual, which jax 0.4.x
        # partial-eval mislabels with dim-0 axis names and the backward
        # pass then rejects (_SpecError).
        return (lax.psum(loss_sum, stage_axis)[None],
                lax.psum(tok_sum, stage_axis)[None])

    # check_vma=False: the model's inner scans allocate fresh (pod-
    # invariant) carries which the varying-axis type system would reject;
    # semantics are unaffected (ppermute/psum behave classically)
    loss_sum, tok_sum = shard_map(
        staged, mesh=mesh,
        in_specs=(P(stage_axis), P(), P(), P(), P()),
        out_specs=(P(), P()),
        axis_names={stage_axis},
        check_vma=False,
    )(params["blocks"],
      {"embed_tokens": params["embed_tokens"]},
      {"final_norm": params["final_norm"],
       **({"lm_head": params["lm_head"]} if "lm_head" in params
          else {"embed_tokens": params["embed_tokens"]})},
      tok_mb, lab_mb)
    loss = loss_sum[0] / jnp.maximum(tok_sum[0], 1.0)
    return loss, {"ce": loss, "aux": jnp.float32(0.0)}


def pp_param_specs(params: Any, cfg: ModelConfig, mesh_cfg,
                   stage_axis: str = "pod"):
    """Baseline specs + the blocks' scan dim sharded over the stage axis
    (each pod stores only its stage — optimizer state follows)."""
    from repro.distributed.sharding import param_specs

    base = param_specs(params, cfg, mesh_cfg)

    def visit(path, spec):
        keys = [str(getattr(p, "key", p)) for p in path]
        if "blocks" in keys and len(spec) > 0:
            return P(stage_axis, *spec[1:])
        return spec

    return jax.tree_util.tree_map_with_path(visit, base)


def make_pp_train_step(cfg: ModelConfig, tcfg, *, mesh,
                       n_microbatches: int, stage_axis: str = "pod"):
    """PP train step (AdamW update shared with the standard path)."""
    from repro.optim.adamw import adamw_update
    from repro.train.train_step import TrainState

    # inner jit is load-bearing on jax 0.4.x: differentiating the raw
    # shard_map hits a partial-eval path that mislabels rank-0 residuals
    # (_SpecError); grad-of-jit takes the pjit path, which is sound
    loss_jit = jax.jit(lambda p, b: gpipe_loss_fn(
        cfg, p, b, mesh=mesh, n_microbatches=n_microbatches,
        stage_axis=stage_axis, remat=tcfg.remat_policy))

    def train_step(state: TrainState, batch: dict):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_jit(p, batch), has_aux=True)(state.params)
        new_params, new_opt, om = adamw_update(state.params, grads,
                                               state.opt, tcfg)
        return TrainState(new_params, new_opt, state.residual), \
            dict(metrics, loss=loss, **om)

    return train_step
