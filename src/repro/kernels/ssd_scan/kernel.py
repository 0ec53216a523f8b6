"""Chunked SSD (Mamba-2 state-space duality) scan.

The SSD decomposition is itself the paper's decoupled pattern: the
quadratic *intra-chunk* term is independent per chunk (parallel producer),
while the (P × N) *inter-chunk* state pass is a tiny sequential consumer —
on TPU the state carry lives in VMEM scratch across a sequential grid axis,
so the MXU-heavy intra-chunk GEMMs of chunk c+1 overlap the state fold of
chunk c in the pipelined grid (the same overlap MR-1S gets from its
chunked push).

Per grid step the working set is one chunk: x (c × P), B/C (c × N), the
(c × c) decay matrix and the (P × N) state — c = 256, P = 64, N = 128 is
~0.6 MB fp32, VMEM-friendly; all contraction dims are 64/128/256 so the
MXU stays dense.

Grid: (B*H, n_chunks) — chunks sequential (state dependency).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state, *,
                chunk: int):
    ic = pl.program_id(1)
    n_c = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0].astype(jnp.float32)               # (c, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)       # (c,)
    A = a_ref[0, 0]                                # scalar (negative)
    Bm = b_ref[0].astype(jnp.float32)              # (c, N)
    Cm = c_ref[0].astype(jnp.float32)              # (c, N)

    dA = dt * A                                    # (c,)
    cum = jnp.cumsum(dA)                           # (c,)
    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j
    diff = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    s = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (c, c)
    s = s * L
    xdt = x * dt[:, None]                          # (c, P)
    y = jax.lax.dot_general(s, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (c, P)

    # carried-in state contribution: y_inter = (C @ state^T) * exp(cum)
    y += jax.lax.dot_general(Cm, state[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cum)[:, None]
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: state' = state * exp(cum_last) + (xdt * d2e)^T @ B
    decay_to_end = jnp.exp(cum[-1] - cum)          # (c,)
    upd = jax.lax.dot_general(
        xdt * decay_to_end[:, None], Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # (P, N)
    state[...] = state[...] * jnp.exp(cum[-1]) + upd

    @pl.when(ic == n_c - 1)
    def _fin():
        st_ref[0] = state[...]


def ssd_pallas(x, dt, A, B, C, *, chunk: int = 256, interpret: bool = True):
    """x: (BH, S, P); dt: (BH, S, 1); A: (BH, 1); B/C: (BH, S, N).
    Returns (y (BH, S, P), state (BH, P, N) fp32)."""
    BH, S, Pd = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    n_c = -(-S // chunk)
    pad = n_c * chunk - S
    if pad:
        padf = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        x, dt, B, C = padf(x), padf(dt), padf(B), padf(C)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, st = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((BH, n_c * chunk, Pd), x.dtype),
                   jax.ShapeDtypeStruct((BH, Pd, N), jnp.float32)),
        grid=(BH, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, Pd), lambda b, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, ic: (b, ic, 0)),
            pl.BlockSpec((1, 1), lambda b, ic: (b, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ic: (b, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ic: (b, ic, 0)),
        ],
        out_specs=(pl.BlockSpec((1, chunk, Pd), lambda b, ic: (b, ic, 0)),
                   pl.BlockSpec((1, Pd, N), lambda b, ic: (b, 0, 0))),
        scratch_shapes=[pltpu.VMEM((Pd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, B, C)
    return y[:, :S], st
