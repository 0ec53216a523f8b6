"""Tracing and jaxpr-walking utilities for fleetlint.

Everything here is read-only over jaxprs: trace a
:class:`~repro.core.registry.ProgramHandle` to a ClosedJaxpr (nothing
executes — args are ShapeDtypeStructs), walk equations recursively
through higher-order primitives (pjit / shard_map / scan / while / cond
/ custom_* / pallas_call), and summarize source provenance for findings.
"""
from __future__ import annotations

from collections.abc import Iterable, Iterator

import jax
from jax.extend import core as jcore


def trace_handle(handle) -> jcore.ClosedJaxpr:
    """Trace ``handle.fn(*handle.args)`` to a ClosedJaxpr (no execution).

    The flattened invars follow ``handle.arg_paths`` order (pytree-leaf
    order of ``args``); a mismatch means the handle mis-declares its
    interface, which is itself an error worth raising loudly."""
    closed = jax.make_jaxpr(handle.fn)(*handle.args)
    n_in, n_paths = len(closed.jaxpr.invars), len(handle.arg_paths)
    if n_in != n_paths:
        raise ValueError(
            f"{handle.name}: traced {n_in} flat inputs but arg_paths "
            f"names {n_paths} — handle interface out of sync")
    n_out, n_opaths = len(closed.jaxpr.outvars), len(handle.out_paths)
    if n_out != n_opaths:
        raise ValueError(
            f"{handle.name}: traced {n_out} flat outputs but out_paths "
            f"names {n_opaths} — handle interface out of sync")
    return closed


def where_of(eqn) -> str:
    """``file:line (fn)`` provenance of an equation, best effort."""
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


def _jaxprs_in(v) -> Iterator[jcore.Jaxpr]:
    if isinstance(v, jcore.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jcore.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _jaxprs_in(x)


def subjaxprs(params: dict) -> Iterator[jcore.Jaxpr]:
    """Every jaxpr nested in an equation's params (branches, bodies,
    kernels, ...)."""
    for v in params.values():
        yield from _jaxprs_in(v)


def all_eqns(jaxpr: jcore.Jaxpr) -> Iterator:
    """Depth-first over every equation, descending into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in subjaxprs(eqn.params):
            yield from all_eqns(sub)


def find_eqns(closed: jcore.ClosedJaxpr, names: Iterable[str]) -> list:
    names = frozenset(names)
    return [e for e in all_eqns(closed.jaxpr) if e.primitive.name in names]


def contains_primitive(jaxpr: jcore.Jaxpr, names: Iterable[str]) -> bool:
    names = frozenset(names)
    return any(e.primitive.name in names for e in all_eqns(jaxpr))
