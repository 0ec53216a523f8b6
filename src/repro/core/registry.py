"""Backend registry — pluggable MapReduce engines behind one protocol.

The paper compares two engines (decoupled MR-1S vs bulk-synchronous
MR-2S); this module makes "engine" a first-class, extensible concept
instead of a hardcoded ``"1s"|"2s"`` string branch:

  * :class:`Backend` — the protocol every engine implements: a segmented
    ``make_segment_fns`` triple, so the job API and the checkpoint /
    fault-tolerance layers consume one interface regardless of engine (a
    one-shot job is one segment).
  * :func:`register_backend` — class decorator; the built-in engines
    register themselves as ``"1s"`` and ``"2s"`` on import.
  * :func:`get_backend` / :func:`available_backends` — resolution, with
    a clear error listing what exists when a name is unknown.

``JobSpec`` (the static engine settings) lives here because it is part
of the backend interface, shared by every engine.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from collections.abc import Callable
from typing import Protocol, runtime_checkable

# built-in engines register lazily on first resolution so importing the
# registry stays cheap (no jax compile machinery pulled in for --help paths)
_BUILTIN_MODULES = {
    "1s": "repro.core.onesided",
    "2s": "repro.core.twosided",
}
_REGISTRY: dict[str, type] = {}


@dataclass(frozen=True)
class JobSpec:
    """Static engine settings (paper: Init(filename, win_size, chunk_size,
    task_size, ...))."""
    vocab: int                   # dense Key-Value window size ("win_size")
    task_size: int               # elements per Map task
    push_cap: int                # records per one-sided push per owner
                                 #   ("maximum bytes per one-sided operation")
    n_procs: int
    combine_capacity: int = 0    # 0 -> vocab (two-lane keys: the
                                 #   job's token slots, which bound its
                                 #   distinct keys)
    segment: int = 0             # checkpoint segment (tasks between syncs)
    stealing: bool = False       # device-side work stealing (core/steal.py);
                                 #   only engines advertising
                                 #   ``supports_stealing`` honor it
    code_rate: int = 1           # r-replicated coded shuffle (core/coded.py
                                 #   + distributed/collectives.coded_exchange):
                                 #   every map task runs on r consecutive
                                 #   ranks and the intra-group bucket push is
                                 #   one XOR-coded multicast block instead of
                                 #   r-1 unicasts. 1 = today's path,
                                 #   bit-identical. A comparing field: the
                                 #   coded step is a different compiled
                                 #   program. Only engines advertising
                                 #   ``supports_coded`` honor r > 1.
    # cross-job co-scheduling (core/workdomain.py): a WorkDomain merges
    # K program-compatible jobs into ONE engine program over a composite
    # task/key space. ``coslots`` is K (1 = ordinary solo job) and
    # ``costride`` the task-id stride between member jobs: composite
    # task id = slot * costride + local_id, composite key =
    # slot * (vocab // coslots) + key. Both compare: a co-scheduled
    # program routes records per-slot, so it is a distinct compiled
    # program. Only engines advertising ``supports_coschedule`` accept
    # coslots > 1.
    coslots: int = 1
    costride: int = 0
    # reduce-side key→owner strategy name (core/partition.py). The owner
    # map itself is CARRY DATA, so the compiled program is identical for
    # every partitioner — compare=False keeps this provenance tag out of
    # eq/hash and therefore out of the backends' jit-program memo keys
    # (one compiled engine really does serve every map); checkpoint
    # compat checks read the attribute directly.
    partitioner: str = field(default="hash", compare=False)
    # two-lane keys (a (major, minor) pair, e.g. (word, document)): the
    # window is a LogWindow of ``log_steps`` regions, one per engine step
    # a rank runs, instead of a dense table. Both compare: the log's
    # shape is the compiled carry's. Only engines advertising
    # ``supports_two_lane_keys`` accept key_lanes == 2.
    key_lanes: int = 1
    log_steps: int = 0

    def __post_init__(self):
        if self.key_lanes not in (1, 2):
            raise ValueError(f"key_lanes must be 1 or 2, got "
                             f"{self.key_lanes}")
        if not self.combine_capacity:
            object.__setattr__(
                self, "combine_capacity",
                self.vocab if self.key_lanes == 1
                else self.n_procs * self.log_steps * self.task_size)
        if self.code_rate < 1:
            raise ValueError(f"code_rate must be >= 1, got {self.code_rate}")
        if self.code_rate > 1:
            if self.n_procs % self.code_rate:
                raise ValueError(
                    f"code_rate={self.code_rate} needs n_procs divisible "
                    f"into r-rank code groups (got n_procs={self.n_procs})")
            if self.coslots > 1:
                raise ValueError(
                    "co-scheduling (coslots > 1) does not compose with "
                    "code_rate > 1 — the fleet cursor claims single task "
                    "slots, which would break the r-group decode")
        if self.coslots > 1:
            if self.costride <= 0:
                raise ValueError("coslots > 1 needs a positive costride")
            if self.vocab % self.coslots:
                raise ValueError(
                    f"co-scheduled vocab {self.vocab} must be "
                    f"coslots={self.coslots} equal per-job windows")


# map_fn(task_tokens, task_id, repeat) -> (keys, values); built from a
# UseCase by repro.core.usecase.as_map_fn.
MapFn = Callable


@runtime_checkable
class Backend(Protocol):
    """What every engine provides: the segmented triple, wired by
    ``(spec, map_fn, mesh)``; ``map_fn`` has the signature
    ``map_fn(task_tokens, task_id, repeat) -> (keys, values)``."""

    name: str

    def make_segment_fns(self, spec: JobSpec, map_fn: MapFn, mesh):
        """Returns ``(init_fn, segment_fn, finish_fn)``, each jitted over
        the mesh, sharing the :class:`~repro.core.windows.EngineCarry`
        carry type — ``segment_fn(carry, tok, tid, rep)`` advances a
        segment; the host may snapshot the carry between calls (the
        paper's per-task window sync)."""
        ...


class UnknownBackendError(KeyError):
    pass


def register_backend(name: str):
    """Class decorator: ``@register_backend("1s")`` makes the engine
    resolvable by name through :func:`get_backend`."""
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def _ensure_builtins():
    for name, module in _BUILTIN_MODULES.items():
        if name not in _REGISTRY:
            importlib.import_module(module)


_INSTANCES: dict[str, Backend] = {}


def get_backend(name: str) -> Backend:
    """Resolve a backend name to its (singleton) engine instance —
    singletons so the engines' jitted-program caches persist across
    jobs."""
    if name not in _REGISTRY and name in _BUILTIN_MODULES:
        importlib.import_module(_BUILTIN_MODULES[name])
    if name not in _REGISTRY:
        _ensure_builtins()
        raise UnknownBackendError(
            f"unknown backend {name!r}; available: {available_backends()}")
    if name not in _INSTANCES:
        _INSTANCES[name] = _REGISTRY[name]()
    return _INSTANCES[name]


# ---------------------------------------------------------------------------
# traceable program handles (consumed by repro.analysis — fleetlint)
# ---------------------------------------------------------------------------

# The engines' replication contract, by flattened argument/output path.
# Everything here is *asserted* replicated across ranks by the engine
# design (psum-maintained progress rows, carried owner maps, psum'd
# overflow totals); fleetlint's REP001 rule proves it from the jaxpr.
# ``carry.job_work`` is the cross-job executed-work row (one slot per
# co-scheduled member job) — psum-maintained exactly like ``carry.work``
# so every rank agrees on how much of each tenant's work actually ran.
ENGINE_REPLICATED_CARRY = ("carry.status", "carry.cursor", "carry.work",
                           "carry.stolen", "carry.job_work",
                           "carry.owner_map", "carry.owner_split")


@dataclass(frozen=True)
class ProgramHandle:
    """One traceable SPMD program: enough to ``jax.make_jaxpr`` it and to
    interpret the flattened inputs/outputs by name.

    ``fn(*args)`` must be traceable with ``args`` (ShapeDtypeStructs are
    fine — nothing executes). ``arg_paths``/``out_paths`` name the
    *flattened* (tree-leaf order) inputs/outputs; ``replicated_in`` /
    ``replicated_out`` are the subset the backend asserts replicated
    across ``allowed_axes`` — the analyzer's REP001 obligation."""
    name: str
    fn: Callable
    args: tuple
    arg_paths: tuple[str, ...]
    out_paths: tuple[str, ...]
    replicated_in: tuple[str, ...] = ()
    replicated_out: tuple[str, ...] = ()
    allowed_axes: tuple[str, ...] = ("procs",)


def segment_program_handles(backend: Backend, spec: JobSpec,
                            map_fn: MapFn, mesh, seg_tasks: int = 2,
                            tag: str = "") -> tuple[ProgramHandle, ...]:
    """Build :class:`ProgramHandle`\\ s for a backend's segmented triple.

    Shared by every backend whose segmented path speaks
    :class:`~repro.core.windows.EngineCarry` (both built-ins do); a
    backend with a different carry overrides ``trace_handles`` wholesale.
    Nothing is executed — args are ShapeDtypeStructs and the carry
    structure comes from ``jax.eval_shape(init_fn)``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.windows import EngineCarry

    init_fn, seg_fn, fin_fn = backend.make_segment_fns(spec, map_fn, mesh)
    carry_shapes = jax.eval_shape(init_fn)
    P, S = spec.n_procs, spec.task_size
    tok = jax.ShapeDtypeStruct((P, seg_tasks, S), jnp.int32)
    tid = jax.ShapeDtypeStruct((P, seg_tasks), jnp.int32)
    rep = jax.ShapeDtypeStruct((P, seg_tasks), jnp.int32)

    carry_paths = tuple(f"carry.{f}" for f in EngineCarry._fields)
    if not tag:
        fn_name = getattr(map_fn, "__name__", "map_fn")
        tag = f"{backend.name}/{fn_name}"
    return (
        ProgramHandle(
            name=f"{tag}/init", fn=init_fn, args=(),
            arg_paths=(), out_paths=carry_paths,
            replicated_out=ENGINE_REPLICATED_CARRY),
        ProgramHandle(
            name=f"{tag}/segment", fn=seg_fn,
            args=(carry_shapes, tok, tid, rep),
            arg_paths=carry_paths + ("tokens", "task_ids", "repeats"),
            out_paths=carry_paths,
            replicated_in=ENGINE_REPLICATED_CARRY,
            replicated_out=ENGINE_REPLICATED_CARRY),
        ProgramHandle(
            name=f"{tag}/finish", fn=fin_fn, args=(carry_shapes,),
            arg_paths=carry_paths,
            out_paths=("keys", "values", "combine_overflow"),
            replicated_in=ENGINE_REPLICATED_CARRY,
            replicated_out=("combine_overflow",)),
    )


def memoized(cache: dict, key, builder):
    """Tiny jit-program memo helper for backends; falls back to building
    uncached when the key is unhashable."""
    try:
        hit = cache.get(key)
    except TypeError:
        return builder()
    if hit is None:
        cache[key] = hit = builder()
    return hit


def available_backends():
    _ensure_builtins()
    return sorted(_REGISTRY)
