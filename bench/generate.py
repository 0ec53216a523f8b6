"""The one traffic generator: every mix in ``bench/traffic/`` is data for it.

A mix file holds ``keys``, the law the input's key ids follow over
``[0, vocab)`` of the configuration's use case. ``{"law": "zipf",
"exponent": a}`` is Zipf's law truncated to the vocabulary (rank k has
weight k^-a and id k-1); ``{"law": "shares", "shares": [...]}`` gives each
id's weight outright (a share, or a count the shares are taken from).

Tokens are drawn on the device from ``--seed`` by inverse CDF over an
integer-scaled CDF: the law's cumulative shares, worked out in float64 on
the host, become ``vocab - 1`` uint32 boundaries, and a uniform 32-bit
draw u takes the id of the first boundary above it. Every id keeps a range
of at least one draw (the last rank of the Zipf(1) law over 2^22 ids has
about 64 of the 2^32), so the tail stays reachable. The draw runs in
chunks far smaller than a job's device footprint, and each chunk comes to
host memory once. The same seed gives the same tokens on any backend.
"""
from __future__ import annotations

import numpy as np

CHUNK = 1 << 22


def cdf_bounds(keys: dict, vocab: int) -> np.ndarray:
    """The ``vocab - 1`` uint32 boundaries of the law ``keys``."""
    law = keys["law"]
    if law == "zipf":
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(
            keys["exponent"])
    elif law == "shares":
        w = np.asarray(keys["shares"], np.float64)
        if len(w) != vocab or (w <= 0).any():
            raise ValueError(f"shares must give each of the {vocab} ids a "
                             f"positive share, got {keys['shares']}")
    else:
        raise ValueError(f"unknown key law {law!r}")
    cdf = np.cumsum(w) / w.sum()
    bounds = np.floor(cdf[:-1] * 2.0 ** 32).astype(np.uint64)
    if (np.diff(bounds) == 0).any() or (bounds.size and bounds[0] == 0):
        raise ValueError("an id's share is below 2^-32: it could never "
                         "be drawn")
    return bounds.astype(np.uint32)


def _seed_key(seed: int):
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {seed}")
    key = jax.random.key(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _chunk_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key, index, bounds):
        bits = jax.random.bits(jax.random.fold_in(key, index), (CHUNK,),
                               jnp.uint32)
        # "sort" places the whole chunk in one sort with the boundaries;
        # the binary search ("scan") takes log2(vocab) gathers per token
        # and ran six times slower on a v5e at vocab 2^22
        return jnp.searchsorted(bounds, bits, side="right",
                                method="sort").astype(jnp.int32)
    return draw


def make_tokens(keys: dict, vocab: int, n_tokens: int,
                seed: int) -> np.ndarray:
    """``n_tokens`` int32 key ids drawn on the default device from
    ``seed``, returned in host memory."""
    import jax
    bounds = jax.device_put(cdf_bounds(keys, vocab))
    key = _seed_key(seed)
    draw = _chunk_fn()
    out = np.empty((n_tokens,), np.int32)
    for i, lo in enumerate(range(0, n_tokens, CHUNK)):
        hi = min(lo + CHUNK, n_tokens)
        out[lo:hi] = np.asarray(draw(key, np.uint32(i), bounds))[: hi - lo]
    return out

