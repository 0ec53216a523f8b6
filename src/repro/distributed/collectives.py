"""Collective helpers shared by the MapReduce engine and the MoE layer.

Everything here runs *inside* ``shard_map`` regions (named-axis
collectives). Values there carry jax's varying-manual-axes (VMA) type:
``pvary`` and ``match_vma`` cast fresh constants so that loop carries
enter with the type they leave with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def pvary(x, axis):
    """Mark fresh constants as axis-varying inside shard_map regions
    (required by the VMA type system for scan carries that meet
    collective outputs)."""
    return jax.tree.map(lambda a: lax.pcast(a, (axis,), to="varying"), x)


def match_vma(x, *refs):
    """Cast every leaf of ``x`` to vary over each manual axis that any of
    ``refs`` varies over; leaves already varying keep their type. Outside
    shard_map nothing varies and ``x`` comes back unchanged."""
    axes = frozenset().union(*(jax.typeof(r).vma for r in refs))

    def cast(a):
        missing = tuple(sorted(axes - jax.typeof(a).vma))
        return lax.pcast(a, missing, to="varying") if missing else a

    return jax.tree.map(cast, x)


def all_to_all_blocks(x: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Exchange equal blocks: x has leading dim P (one block per peer).

    Row j of the result is the block rank j addressed to us. This is the
    JAX-native carrier for the paper's bucketed shuffle (MPI_Alltoallv with
    fixed-capacity buckets).
    """
    P = lax.axis_size(axis)
    assert x.shape[0] == P, (x.shape, P)
    return lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)


def coded_exchange(bk: jnp.ndarray, bv: jnp.ndarray, axis: str,
                   code_rate: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One XOR-coded multicast step of the bucket shuffle (Coded
    MapReduce, arXiv 1512.01625; host half in ``repro.core.coded``).

    ``bk``/``bv`` are the (P, cap) per-destination buckets that every
    member of an r-rank code group computed *identically* (the group
    maps the same replicated task block). Instead of unicasting r-1
    bucket rows to its group peers, each member ships ONE coded block —
    the XOR of the buckets destined for its peers — and each receiver
    decodes its own bucket from its designated peer's block by XOR-ing
    back the side information it mapped locally. Inter-group rows are
    deduplicated to a single speaker per destination (member ``q % r``
    of every group speaks for destination ``q``), so with the Combine
    dup-sum each record still folds exactly once fleet-wide.

    Returns the (P, cap) pending rows ready to fold: the decoded bucket
    on the designated-peer row, speaker buckets as received, and every
    other row (raw coded blocks, the self row, silent non-speakers)
    cleared to sentinel-empty.
    """
    from functools import reduce

    from repro.core.kv import KEY_SENTINEL
    r = int(code_rate)
    P = lax.axis_size(axis)
    assert r > 1 and P % r == 0, (P, r)
    me = lax.axis_index(axis)
    g, m = me // r, me % r
    q = jnp.arange(P)
    in_group = (q // r) == g
    peer = in_group & (q != me)

    def _xor(x, mask):
        rows = jnp.where(mask[:, None], x, 0)
        return reduce(jnp.bitwise_xor, [rows[i] for i in range(P)])

    # encode: X = XOR of the buckets destined for my r-1 group peers
    xk, xv = _xor(bk, peer), _xor(bv, peer)
    speak = (~in_group) & ((q % r) == m)
    sk = jnp.where(peer[:, None], xk[None, :],
                   jnp.where(speak[:, None], bk, KEY_SENTINEL))
    sv = jnp.where(peer[:, None], xv[None, :],
                   jnp.where(speak[:, None], bv, 0))
    gk = all_to_all_blocks(sk, axis)
    gv = all_to_all_blocks(sv, axis)
    # decode my bucket from the designated peer's coded block: its XOR
    # covers the whole group but the sender, so XOR-ing the locally
    # mapped buckets of everyone else leaves exactly the one for me
    d = g * r + (m + 1) % r
    side = in_group & (q != me) & (q != d)
    dk = gk[d] ^ _xor(bk, side)
    dv = gv[d] ^ _xor(bv, side)
    is_d = (q == d)[:, None]
    rk = jnp.where(in_group[:, None],
                   jnp.where(is_d, dk[None, :], KEY_SENTINEL), gk)
    rv = jnp.where(in_group[:, None],
                   jnp.where(is_d, dv[None, :], 0), gv)
    return rk, rv


def ring_send_right(x: jnp.ndarray, axis: str, shift: int = 1) -> jnp.ndarray:
    P = lax.axis_size(axis)
    perm = [(i, (i + shift) % P) for i in range(P)]
    return lax.ppermute(x, axis, perm)


def tree_gather_permute(x, axis: str, level: int):
    """collective_permute used by the combine tree: at ``level`` l, rank
    i + 2**l sends its payload to rank i (for i multiple of 2**(l+1))."""
    P = lax.axis_size(axis)
    stride = 1 << level
    perm = []
    for i in range(0, P, stride * 2):
        if i + stride < P:
            perm.append((i + stride, i))
    return lax.ppermute(x, axis, perm)


def psum_dp(x, mesh_cfg):
    """psum over all data-parallel axes (pod + data) under shard_map."""
    for ax in mesh_cfg.dp_axes:
        x = lax.psum(x, ax)
    return x
