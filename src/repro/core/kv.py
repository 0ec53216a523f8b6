"""Key-value record machinery: hashing, ownership, sort-based local reduce.

The paper encodes variable-length ``<h|key|value>`` records and owns each key
by a 64-bit hash. On TPU we keep fixed-width int32 records (variable-length
keys are resolved to ids by the ingest tokenizer — see DESIGN.md §2.1) and a
bijective 32-bit mixing hash (Murmur3-style finalizer) for ownership, which
preserves the paper's "uniformly spread keys across owners" property.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

KEY_SENTINEL = jnp.iinfo(jnp.int32).max  # marks an empty / invalid record


def mix32(x: jnp.ndarray) -> jnp.ndarray:
    """Murmur3 fmix32 — bijective on uint32."""
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def owner_of(keys: jnp.ndarray, n_procs: int) -> jnp.ndarray:
    """hash(key) % P — the paper's ownership rule."""
    return (mix32(keys) % jnp.uint32(n_procs)).astype(jnp.int32)


def local_reduce(keys: jnp.ndarray, values: jnp.ndarray, capacity: int):
    """Paper phase II (Local Reduce): aggregate duplicate keys.

    Sorts by key and segment-sums, returning ``capacity`` records
    (key ascending, KEY_SENTINEL padding). With :func:`bucketize` it is
    the reference composition of the engine step, which runs
    :func:`reduce_and_bucketize` instead; it stays the reduce of
    ``combine_records``, ``merge_sorted`` and the fused kernel's oracle.
    """
    order = jnp.argsort(keys)
    sk = keys[order]
    sv = values[order]
    valid = sk != KEY_SENTINEL
    # head of each run of equal keys
    head = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]]) & valid
    seg = jnp.cumsum(head) - 1                      # segment id per element
    # ghost slot ``capacity`` for invalid / non-head writes, so slot
    # capacity-1 is never clobbered when n_unique == capacity
    seg = jnp.where(valid, seg, capacity)
    sums = jnp.zeros((capacity + 1,), values.dtype).at[seg].add(
        jnp.where(valid, sv, 0))
    uk = jnp.full((capacity + 1,), KEY_SENTINEL, keys.dtype).at[
        jnp.where(head, seg, capacity)
    ].set(jnp.where(head, sk, KEY_SENTINEL))
    n_unique = jnp.sum(head)
    idx = jnp.arange(capacity)
    uk = jnp.where(idx < n_unique, uk[:capacity], KEY_SENTINEL)
    sums = jnp.where(idx < n_unique, sums[:capacity], 0)
    return uk, sums, n_unique


def local_reduce_repeated(keys, vals, capacity: int, rep):
    """Paper footnote 5 imbalance model: the task is *computed* ``rep``
    times while its input is read once; the result is identical for any
    rep >= 1.

    Each extra repetition re-runs a full local_reduce (the task's compute)
    seeded with a value-preserving dependency on the previous iteration —
    ``uv < 0`` is never true in value but XLA cannot prove it, so the loop
    body can be neither CSE'd nor dead-code-eliminated."""
    uk0, uv0, _ = local_reduce(keys, vals, capacity)

    def body(i, carry):
        uk, uv = carry
        k_dep = jnp.where(uv < 0, uk, KEY_SENTINEL)
        v_dep = jnp.where(uv < 0, uv, 0)
        uk2, uv2, _ = local_reduce(jnp.concatenate([keys, k_dep]),
                                   jnp.concatenate([vals, v_dep]), capacity)
        return uk2, uv2

    return lax.fori_loop(1, jnp.maximum(rep, 1), body, (uk0, uv0))


def merge_sorted(keys_a, vals_a, keys_b, vals_b, capacity: int):
    """Merge two key-ascending unique record arrays, summing duplicates."""
    k = jnp.concatenate([keys_a, keys_b])
    v = jnp.concatenate([vals_a, vals_b])
    return local_reduce(k, v, capacity)[:2]


def bucketize(keys, values, n_procs: int, cap: int, owners=None):
    """Scatter records into per-owner buckets — the paper's one-sided put
    target layout: (P, cap) records + per-owner fill counts.

    ``owners`` overrides the default ``hash(key) % P`` rule with a
    precomputed per-record owner vector (values in [0, n_procs]; the
    skew-aware maps of :mod:`repro.core.partition` resolve it from the
    carried owner map). Records beyond ``cap`` for a hot owner are
    *dropped from the push* and reported in ``overflow`` so the caller
    can retain them locally (the paper's ownership-transfer semantics,
    footnote 2).
    """
    if owners is None:
        owners = owner_of(keys, n_procs)
    valid = keys != KEY_SENTINEL
    owners = jnp.where(valid, owners, n_procs)      # invalid -> ghost bucket
    order = jnp.argsort(owners, stable=True)
    so, sk, sv = owners[order], keys[order], values[order]
    # position within its bucket
    one = jnp.ones_like(so)
    pos_in_owner = jnp.cumsum(one) - 1
    start = jnp.searchsorted(so, jnp.arange(n_procs + 1))
    pos = pos_in_owner - start[jnp.clip(so, 0, n_procs)]
    counts = jnp.minimum(start[1:] - start[:-1], cap)[:n_procs]
    in_cap = (pos < cap) & (so < n_procs)
    flat_idx = jnp.where(in_cap, so * cap + pos, n_procs * cap)
    bk = jnp.full((n_procs * cap + 1,), KEY_SENTINEL, keys.dtype).at[flat_idx].set(
        jnp.where(in_cap, sk, KEY_SENTINEL)
    )[:-1].reshape(n_procs, cap)
    bv = jnp.zeros((n_procs * cap + 1,), values.dtype).at[flat_idx].set(
        jnp.where(in_cap, sv, 0)
    )[:-1].reshape(n_procs, cap)
    overflow_k = jnp.where(in_cap | (so >= n_procs), KEY_SENTINEL, sk)
    overflow_v = jnp.where(in_cap | (so >= n_procs), 0, sv)
    return bk, bv, counts, (overflow_k, overflow_v)


# ---------------------------------------------------------------------------
# the engine step's Local Reduce + bucketize, in one keyed sort
# ---------------------------------------------------------------------------

def _scan(x: jnp.ndarray, op) -> jnp.ndarray:
    """Inclusive prefix ``op`` (``jnp.add`` or ``jnp.maximum``) along the
    last axis of a nonnegative int32 ``(k, n)`` array.

    One reduce-window runs along lanes of 128 and doubling steps carry
    the row totals across rows. A single long reduce-window is what
    ``jnp.cumsum`` gives, and the TPU compiler rewrites that into ops
    without the caller's scope, so a phase's prefix sums would read as
    unscoped time.
    """
    k, n = x.shape
    lanes = 128
    rows = -(-n // lanes)
    r = jnp.pad(x, ((0, 0), (0, rows * lanes - n))).reshape(k, rows, lanes)
    r = (lax.cumsum if op is jnp.add else lax.cummax)(r, axis=2)
    carry = r[:, :, -1]
    step = 1
    while step < rows:
        carry = op(carry, jnp.pad(carry, ((0, 0), (step, 0)))[:, :-step])
        step *= 2
    before = jnp.pad(carry, ((0, 0), (1, 0)))[:, :-1]
    return op(r, before[:, :, None]).reshape(k, -1)[:, :n]


def _run_sums(new: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
    """Inclusive sums of ``values`` from the head of each run (``new``
    marks run heads), so each run's last element holds its total; exact
    mod 2^32 like a scatter-add, with no scatter.

    The values are cut into limbs of b bits, b the largest that keeps a
    limb's prefix sum over the whole array below 2^31. Each limb's prefix
    sum is then nondecreasing, so the prefix sum before the current run's
    head is a running max of that sum taken at the heads.
    """
    n = values.shape[0]
    b = ((2 ** 31 - 1) // max(n, 1) + 1).bit_length() - 1
    shifts = range(0, 32, b)
    u = values.astype(jnp.uint32)
    limbs = jnp.stack([((u >> s) & jnp.uint32((1 << min(b, 32 - s)) - 1))
                       .astype(jnp.int32) for s in shifts])
    c = _scan(limbs, jnp.add)
    run = c - _scan(jnp.where(new, c - limbs, 0), jnp.maximum)
    total = jnp.zeros_like(u)
    for j, s in enumerate(shifts):
        total = total + (run[j].astype(jnp.uint32) << s)
    return total.astype(values.dtype)


def _lanes(keys) -> tuple:
    """The lanes of a key array: a two-lane key is a (major, minor) tuple
    of int32 arrays, a one-lane key a single array."""
    return tuple(keys) if isinstance(keys, tuple) else (keys,)


def _like(keys, lanes):
    """``lanes`` in the form of ``keys``: a tuple for two-lane keys, the
    one array otherwise."""
    return tuple(lanes) if isinstance(keys, tuple) else lanes[0]


def _sort_runs(lanes, values):
    """One sort of the records on the sort lanes ``lanes`` (major first),
    values carried along; returns the sorted lanes, the run-head flags and
    each run's inclusive sums."""
    *sl, sv = lax.sort((*lanes, values), num_keys=len(lanes))
    first = jnp.ones((1,), bool)
    diff = sl[0][1:] != sl[0][:-1]
    for s in sl[1:]:
        diff = diff | (s[1:] != s[:-1])
    new = jnp.concatenate([first, diff])
    return sl, new, _run_sums(new, sv), sv


def reduce_runs(keys, vals, owners, n_procs: int, rep=1):
    """The engine step's Local Reduce: one sort of the raw records on
    (owner, key lanes), each run's total at its last record.

    ``keys`` is one int32 array, or a (major, minor) tuple for two-lane
    keys, whose major lane marks empty records with KEY_SENTINEL.
    ``owners`` is per raw record, in [0, n_procs]; n_procs is the ghost
    owner, whose records sort last and are dropped, as are sentinel keys.
    Returns the sorted owners, the sorted key lanes (a tuple), the run
    heads and tails, and the inclusive run sums. Footnote-5 repeats
    (``rep`` > 1) re-sort and re-sum the task with
    :func:`local_reduce_repeated`'s dependency: a run whose previous total
    is negative adds that total once more.
    """
    P = n_procs
    lanes = _lanes(keys)
    owners = jnp.where(lanes[0] != KEY_SENTINEL, owners, P)
    lanes = tuple(jnp.where(owners < P, k, KEY_SENTINEL) for k in lanes)
    with jax.named_scope("local_reduce"):
        (so, *sk), new, sums, sv0 = _sort_runs((owners, *lanes), vals)
        last = jnp.concatenate([new[1:], jnp.ones((1,), bool)])

        def body(_, carry):
            # the records are sorted already, so the sort leaves owners
            # and keys as they are and sv0 stays aligned with them; equal
            # keys may trade values, which leaves each run's total as is
            so_, sk_, sums_ = carry
            dep = jnp.where(last & (sums_ < 0), sums_, 0)
            (so2, *sk2), _, sums2, _ = _sort_runs((so_, *sk_), sv0 + dep)
            return so2, tuple(sk2), sums2

        so, sk, sums = lax.fori_loop(1, jnp.maximum(rep, 1), body,
                                     (so, tuple(sk), sums))
    return so, sk, new, last, sums


def reduce_and_bucketize(keys, vals, owners, n_procs: int, cap: int,
                         rep=1):
    """The engine step's Local Reduce + bucketize: exactly
    ``bucketize(*local_reduce_repeated(keys, vals, S, rep), n_procs, cap,
    owners=<owners of the unique keys>)`` — the same ``(P, cap)`` buckets
    and ``counts``, and overflow that folds into a window identically —
    from one sort of the raw records (:func:`reduce_runs`).

    One sort suffices because the owner of a record depends only on its
    key (and the task): :func:`bucketize`'s stable owner sort of the
    key-ascending unique records is the (owner, key) order, so the raw
    records sorted on (owner, key) hold every key's run in bucket order.
    Two-lane keys (a (major, minor) tuple) sort on (owner, major, minor),
    and their buckets and overflow come back as tuples of lanes.

    Run sums come from prefix sums (:func:`_run_sums`), ranks in a bucket
    from a prefix count of run heads less the owner's first rank; only
    the in-cap runs are scattered into the buckets. The overflow is
    length S in sorted order: each out-of-cap run's total at its last
    record, sentinels elsewhere, as ``DenseWindow.put`` takes it.
    """
    P = n_procs
    so, sk, new, last, sums = reduce_runs(keys, vals, owners, P, rep)
    with jax.named_scope("route"):
        live = so < P
        head = new & live
        tail = last & live
        # rank among all live runs, less the owner's first rank: the
        # number of live runs of the owners below it
        rank = _scan(head[None].astype(jnp.int32), jnp.add)[0] - 1
        owner = jnp.arange(P)[:, None]
        first = jnp.sum(head & (so < owner), axis=1, dtype=jnp.int32)
        pos = rank - jnp.sum(jnp.where(so == owner, first[:, None], 0),
                             axis=0)
        counts = jnp.minimum(jnp.sum(head & (so == owner), axis=1,
                                     dtype=jnp.int32), cap)
        in_cap = tail & (pos < cap)
        flat = jnp.where(in_cap, so * cap + pos, P * cap)
        bk = [jnp.full((P * cap + 1,), KEY_SENTINEL, k.dtype).at[flat].set(
            jnp.where(in_cap, k, KEY_SENTINEL))[:-1].reshape(P, cap)
            for k in sk]
        bv = jnp.zeros((P * cap + 1,), vals.dtype).at[flat].set(
            jnp.where(in_cap, sums, 0))[:-1].reshape(P, cap)
        spill = tail & ~in_cap
        overflow_k = [jnp.where(spill, k, KEY_SENTINEL) for k in sk]
        overflow_v = jnp.where(spill, sums, 0)
    return (_like(keys, bk), bv, counts,
            (_like(keys, overflow_k), overflow_v))


def sort_reduce(keys, values, capacity: int):
    """:func:`local_reduce` for two-lane keys, from one sort: ``keys`` a
    (major, minor) pair, duplicate keys summed (exactly mod 2^32), the
    records compacted to the front in key order. Returns ``capacity``
    records (a (major, minor) pair, KEY_SENTINEL padding) and the number
    of distinct keys, which may exceed ``capacity``: the records past it
    are lost, and the caller counts them."""
    valid = keys[0] != KEY_SENTINEL
    lanes = tuple(jnp.where(valid, k, KEY_SENTINEL) for k in keys)
    sl, new, sums, _ = _sort_runs(lanes, jnp.where(valid, values, 0))
    tail = (jnp.concatenate([new[1:], jnp.ones((1,), bool)])
            & (sl[0] != KEY_SENTINEL))
    pos = _scan(tail[None].astype(jnp.int32), jnp.add)[0] - 1
    keep = tail & (pos < capacity)
    idx = jnp.where(keep, pos, capacity)
    out = tuple(
        jnp.full((capacity + 1,), KEY_SENTINEL, k.dtype).at[idx].set(
            jnp.where(keep, k, KEY_SENTINEL))[:capacity] for k in sl)
    vals = jnp.zeros((capacity + 1,), values.dtype).at[idx].set(
        jnp.where(keep, sums, 0))[:capacity]
    return out, vals, jnp.sum(tail, dtype=jnp.int32)
