"""Declarative use-case protocol — what a MapReduce scenario provides.

The old API required subclassing :class:`MapReduceJob` and overriding
``map_task`` (which also had to embed the simulated-imbalance work loop).
The redesigned protocol is declarative and engine-agnostic:

  * ``window``   — dense Key-Value window size this scenario needs
                   (the paper's ``win_size``);
  * ``map_emit(tokens, task_id) -> (keys, values)``
                 — pure Map logic: emit fixed-length int32 record arrays
                   (KEY_SENTINEL marks empty slots). Keys MUST lie in
                   [0, window) — records outside the window are silently
                   dropped by the dense Key-Value fold. ``task_id`` is the
                   global task index (-1 for padding tasks), so scenarios
                   may key by position/document, not just by token;
  * ``key_lanes`` *(optional, default 1)*
                 — 2 declares two-lane keys: ``map_emit`` returns
                   ``((major, minor), values)``, two int32 arrays marking
                   empty slots with KEY_SENTINEL in the major lane, and
                   the engine keeps the records in a log window instead
                   of a dense one (only the major lane is bounded by
                   ``window``, which sizes the owner map). A record's
                   key on the host is ``(major << 32) | minor``;
  * ``local_reduce(keys, values)`` *(optional)*
                 — a per-task combiner applied before the engine's own
                   sort-based reduce (the paper fuses Local Reduce into
                   Map; engines always run their exact reduce regardless);
  * ``finalize(records)`` *(optional)*
                 — decode the engine's ``{key: value}`` records (a
                   read-only :class:`~repro.core.records.Records` mapping
                   over the sorted key and value arrays) into the
                   scenario's natural output (arrays, posting lists, ...).

Engines never see a ``UseCase`` — :func:`as_map_fn` adapts one into the
``map_fn(tokens, task_id, repeat)`` callable of the Backend protocol,
attaching the paper's footnote-5 imbalance model (a task is *computed*
``repeat`` times while its input is read once) uniformly for every
scenario instead of per-subclass.
"""
from __future__ import annotations

from collections.abc import Mapping
from typing import Protocol, runtime_checkable

import jax.numpy as jnp
from jax import lax

from repro.core.kv import mix32


@runtime_checkable
class UseCase(Protocol):
    window: int

    def map_emit(self, tokens: jnp.ndarray,
                 task_id: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
        ...


def work_dependency(tokens: jnp.ndarray, repeat: jnp.ndarray) -> jnp.ndarray:
    """Zero-valued scalar carrying a data dependency on ``repeat``
    iterations of real per-token mixing work, so the simulated imbalance
    compute cannot be dead-code-eliminated (paper footnote 5)."""
    def body(i, acc):
        return acc ^ mix32(tokens.astype(jnp.uint32) +
                           jnp.uint32(i)).astype(jnp.int32)

    acc = lax.fori_loop(0, jnp.maximum(repeat, 1), body,
                        (tokens * 0).astype(jnp.int32))
    return (acc & 0).sum()


def _build_map_fn(usecase: UseCase):
    combiner = getattr(usecase, "local_reduce", None)

    def map_fn(tokens, task_id, repeat):
        keys, vals = usecase.map_emit(tokens, task_id)
        vals = vals + work_dependency(tokens, repeat)
        if combiner is not None:
            keys, vals = combiner(keys, vals)
        return keys, vals

    return map_fn


_MAP_FN_CACHE: dict = {}


def as_map_fn(usecase: UseCase):
    """Adapt a UseCase into the Backend protocol's
    ``map_fn(tokens, task_id, repeat) -> (keys, values)``.

    Memoized per (hashable) use-case, so re-submitting the same job hits
    the engines' jit caches instead of recompiling."""
    try:
        fn = _MAP_FN_CACHE.get(usecase)
        if fn is None:
            _MAP_FN_CACHE[usecase] = fn = _build_map_fn(usecase)
        return fn
    except TypeError:                     # unhashable custom use-case
        return _build_map_fn(usecase)


def finalize(usecase, records: Mapping[int, int]):
    fin = getattr(usecase, "finalize", None)
    return fin(records) if fin is not None else records
