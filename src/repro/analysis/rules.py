"""Rule implementations.

| rule    | proves                                                        |
|---------|---------------------------------------------------------------|
| SPMD001 | collectives only name mesh axes in the program's allowed set  |
| SPMD002 | no collective reachable under rank-divergent control flow     |
| REP001  | outputs asserted replicated really are (taint lattice)        |
| PAL001  | BlockSpec index maps stay in bounds for the shipped grid      |
| PAL002  | integer kernel outputs declare a fitting worst-case count     |
| PAL003  | one shared interpret-mode policy; fallbacks match signatures  |

``check_program`` runs SPMD001/SPMD002/REP001 over one
:class:`~repro.core.registry.ProgramHandle`; ``check_kernel`` runs
PAL001..PAL003 over one :class:`KernelCheck`.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
import inspect
import itertools
import math
from collections.abc import Callable

import jax
import jax.numpy as jnp

from repro.analysis import taint, tracer
from repro.analysis.taint import Finding

# -- programs (SPMD001 / SPMD002 / REP001) ----------------------------------


def check_program(handle) -> list[Finding]:
    """Trace one ProgramHandle and run the taint rules over it."""
    closed = tracer.trace_handle(handle)
    return taint.analyze_handle(handle, closed)


# -- kernels (PAL001 / PAL002 / PAL003) -------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelCheck:
    """One kernel entry in the shipping corpus.

    ``build()`` returns ``(fn, args, kwargs)`` — a representative traced
    call. ``worst_count`` declares the largest value any *integer* output
    can legitimately hold (PAL002 requires the declaration and that it
    fits the dtype). ``ops_module``/``kernel_fn`` point PAL003 at the
    wrapper module and the ``module:attr`` pallas entry point."""
    name: str
    build: Callable = dataclasses.field(compare=False)
    worst_count: int | None = None
    ops_module: str | None = None
    kernel_fn: str | None = None


def check_kernel(kc: KernelCheck) -> list[Finding]:
    fn, args, kwargs = kc.build()
    if kwargs:
        fn = functools.partial(fn, **kwargs)
    closed = jax.make_jaxpr(fn)(*args)
    findings = check_block_bounds(closed, kc.name)
    findings += check_int_capacity(closed, kc)
    if kc.ops_module:
        findings += check_ops_module(
            importlib.import_module(kc.ops_module), kc.name)
    if kc.kernel_fn:
        findings += check_kernel_signature(kc.kernel_fn, kc.name)
    return findings


def _grid_points(grid: tuple) -> list:
    """Every grid point when the grid is small; otherwise the corner/mid
    lattice (index maps are near-affine, so extremes catch the bugs)."""
    if math.prod(grid) <= 4096:
        return list(itertools.product(*[range(g) for g in grid]))
    axes = [sorted({0, g // 2, g - 1}) for g in grid]
    return list(itertools.product(*axes))


def _block_dim(entry) -> int:
    # block_shape entries are Blocked(n) / Element(n) / BoundedSlice(n),
    # or Squeezed() for a size-1 squeezed dim
    return int(getattr(entry, "block_size", 1))


def check_block_bounds(closed, program: str) -> list[Finding]:
    """PAL001: evaluate every BlockSpec index map over the shipped grid
    and require each block index to stay inside the array.

    Scalar-prefetch operands are supplied as zeros — the check covers
    the grid sweep exactly and prefetch-dependent maps at one sample
    point (documented limitation)."""
    findings = []
    for eqn in tracer.find_eqns(closed, ("pallas_call",)):
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            continue
        if getattr(gm, "num_dynamic_grid_bounds", 0):
            continue                       # bounds unknown statically
        grid = tuple(g for g in gm.grid if isinstance(g, int))
        if len(grid) != len(gm.grid) or not grid:
            continue
        points = _grid_points(grid)
        for opi, bm in enumerate(gm.block_mappings):
            if bm is None:
                continue
            shape = tuple(bm.array_aval.shape)
            blocks = tuple(_block_dim(b) for b in bm.block_shape)
            if len(shape) != len(blocks):
                continue
            limits = [-(-d // b) for d, b in zip(shape, blocks)]
            cj = bm.index_map_jaxpr
            extra = [jnp.zeros(v.aval.shape, v.aval.dtype)
                     for v in cj.jaxpr.invars[len(grid):]]
            if len(cj.jaxpr.invars) < len(grid):
                continue
            for pt in points:
                idx = jax.core.eval_jaxpr(cj.jaxpr, cj.consts,
                                          *pt, *extra)
                if len(idx) != len(limits):
                    break
                oob = [(d, int(i)) for d, (i, lim)
                       in enumerate(zip(idx, limits))
                       if int(i) < 0 or int(i) >= lim]
                if oob:
                    d, i = oob[0]
                    findings.append(Finding(
                        "PAL001", program, tracer.where_of(eqn),
                        f"operand {opi}: index map sends grid point "
                        f"{pt} to block index {i} on dim {d} (valid "
                        f"range [0, {limits[d]}) for array dim "
                        f"{shape[d]}, block {blocks[d]})"))
                    break                  # one finding per operand
    return findings


def check_int_capacity(closed, kc: KernelCheck) -> list[Finding]:
    """PAL002: every integer output needs a declared worst-case count
    that fits its dtype — silent wraparound is how a 2^31-record count
    reads as negative."""
    findings = []
    for i, v in enumerate(closed.jaxpr.outvars):
        dtype = v.aval.dtype
        if not jnp.issubdtype(dtype, jnp.integer):
            continue
        cap = jnp.iinfo(dtype).max
        if kc.worst_count is None:
            findings.append(Finding(
                "PAL002", kc.name, f"output {i}",
                f"integer accumulator ({dtype}) with no declared "
                "worst-case count — declare KernelCheck.worst_count "
                "or widen the dtype"))
        elif kc.worst_count > cap:
            findings.append(Finding(
                "PAL002", kc.name, f"output {i}",
                f"worst-case count {kc.worst_count} exceeds "
                f"{dtype} capacity {cap} — accumulator can wrap"))
    return findings


def check_ops_module(mod, program: str) -> list[Finding]:
    """PAL003 (policy half): a kernel wrapper module must route
    interpret-mode defaults through the one shared policy in
    ``repro.kernels.backend`` — private ``_on_tpu`` copies are exactly
    the drift this analyzer exists to prevent."""
    from repro.kernels import backend as shared
    findings = []
    where = getattr(mod, "__name__", str(mod))
    if getattr(mod, "_on_tpu", None) is not None:
        findings.append(Finding(
            "PAL003", program, where,
            "module defines a private _on_tpu policy; use "
            "repro.kernels.backend.default_interpret"))
    wrappers = []
    for attr, fn in vars(mod).items():
        if attr.startswith("_") or not callable(fn):
            continue
        if fn is shared.default_interpret or fn is shared.on_tpu:
            continue               # the shared policy itself, re-exported
        if getattr(fn, "__module__", None) != getattr(mod, "__name__", None):
            continue               # imported (e.g. the raw pallas entry
            #                        point, whose True default is fine —
            #                        check_kernel_signature covers it)
        try:
            params = inspect.signature(fn).parameters
        except (TypeError, ValueError):
            continue
        if "interpret" in params:
            wrappers.append((attr, params["interpret"]))
    for attr, param in wrappers:
        if param.default is not None:
            findings.append(Finding(
                "PAL003", program, f"{where}.{attr}",
                f"wrapper defaults interpret={param.default!r}; the "
                "contract is interpret: bool | None = None resolved "
                "via default_interpret"))
    if wrappers and getattr(mod, "default_interpret", None) \
            is not shared.default_interpret:
        findings.append(Finding(
            "PAL003", program, where,
            "wrapper has an interpret parameter but the module does "
            "not use the shared repro.kernels.backend.default_interpret"))
    return findings


def check_kernel_signature(kernel_fn: str, program: str) -> list[Finding]:
    """PAL003 (signature half): the pallas entry point itself must
    accept ``interpret`` so the wrapper's fallback can reach it."""
    modname, attr = kernel_fn.split(":")
    fn = getattr(importlib.import_module(modname), attr)
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return []
    if "interpret" not in params:
        return [Finding(
            "PAL003", program, kernel_fn,
            "pallas entry point has no interpret parameter — the "
            "interpret-mode fallback cannot reach it")]
    return []
