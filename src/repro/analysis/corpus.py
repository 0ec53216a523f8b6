"""The program corpus fleetlint runs over.

Two halves:

  * the *shipping* matrix — every registered backend x use-case program
    (the same admission-time set the multi-tenant scheduler asserts) and
    every pallas kernel in ``kernels/``, all of which must lint clean;
  * the *mutant* corpus — seeded known-bad programs/kernels, one firing
    example and one near-miss per rule, so the pytest gate proves each
    rule both fires and stays quiet (false-positive guard).
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.rules import KernelCheck
from repro.core.registry import JobSpec, ProgramHandle, available_backends, \
    get_backend
from repro.core.usecase import as_map_fn
from repro.core.usecases import Histogram, InvertedIndex, WordCount

# -- shipping matrix --------------------------------------------------------

# one instance per use-case; window sizes stay small — trace time is
# shape-independent and the analyzer never executes anything
SHIPPING_CASES = (
    ("wordcount", WordCount(vocab=512)),
    ("histogram", Histogram(vocab=512, n_bins=64)),
    ("invindex", InvertedIndex(queries=(3, 5, 7), n_docs=4,
                               tasks_per_doc=2)),
)


def procs_mesh(n_procs: int | None = None) -> Mesh:
    """1-D ``("procs",)`` mesh over the visible devices (P=1 is fine —
    collectives trace identically at any size)."""
    devs = jax.devices()
    n = n_procs or len(devs)
    return Mesh(np.array(devs[:n]), ("procs",))


def shipping_programs(mesh: Mesh | None = None,
                      seg_tasks: int = 2) -> list[ProgramHandle]:
    """Every backend x use-case (x stealing variant) as ProgramHandles."""
    if mesh is None:
        mesh = procs_mesh()
    n_procs = int(mesh.devices.size)
    handles: list[ProgramHandle] = []
    for bname in available_backends():
        backend = get_backend(bname)
        for cname, usecase in SHIPPING_CASES:
            variants = [(False, False, "")]
            if getattr(backend, "supports_stealing", False):
                variants.append((True, False, "+steal"))
            if getattr(backend, "supports_fused_map", False):
                # the fused hot path is a different compiled program
                # (a pallas kernel inside the engine scan) — it must
                # pass the same SPMD/replication gate as the unfused one
                variants.append((False, True, "+fused"))
                variants.append((True, True, "+steal+fused"))
            for stealing, fused, suffix in variants:
                spec = JobSpec(vocab=usecase.window, task_size=8,
                               push_cap=16, n_procs=n_procs,
                               segment=seg_tasks, stealing=stealing,
                               fused_map=fused)
                handles.extend(backend.trace_handles(
                    spec, as_map_fn(usecase), mesh, seg_tasks=seg_tasks,
                    tag=f"{bname}/{cname}{suffix}"))
            if getattr(backend, "supports_coded", False) \
                    and n_procs % 2 == 0:
                # the coded exchange (JobSpec.code_rate=2): r-replicated
                # column blocks + the XOR multicast step — a distinct
                # compiled program that must hold the same replication
                # contract. Gated on an even mesh (code groups need
                # r | n_procs): the in-process P=1 run skips it, the
                # P=8 CI analysis job covers it.
                for stealing, suffix in ((False, "+coded"),
                                         (True, "+steal+coded")):
                    spec = JobSpec(vocab=usecase.window, task_size=8,
                                   push_cap=16, n_procs=n_procs,
                                   segment=seg_tasks, stealing=stealing,
                                   code_rate=2)
                    handles.extend(backend.trace_handles(
                        spec, as_map_fn(usecase), mesh,
                        seg_tasks=seg_tasks,
                        tag=f"{bname}/{cname}{suffix}"))
            if getattr(backend, "supports_coschedule", False):
                # the co-scheduled engine: a 2-member WorkDomain's
                # composite program — key-window offsetting plus the
                # psum-maintained ``carry.job_work`` row — ships
                # through the same SPMD/replication gate
                for stealing, suffix in ((False, "+cosched"),
                                         (True, "+steal+cosched")):
                    spec = JobSpec(vocab=usecase.window * 2,
                                   task_size=8, push_cap=16,
                                   n_procs=n_procs, segment=seg_tasks,
                                   stealing=stealing, coslots=2,
                                   costride=seg_tasks)
                    handles.extend(backend.trace_handles(
                        spec, as_map_fn(usecase), mesh,
                        seg_tasks=seg_tasks,
                        tag=f"{bname}/{cname}{suffix}"))
    # the elastic re-mesh fold ships through the same gate as the
    # engines: its replicated-out contract (folded owner map/split +
    # psum checksum) is exactly what REP001 exists to check
    from repro.fleet.remesh import remesh_program_handles
    handles.extend(remesh_program_handles(mesh))
    return handles


def shipping_kernels() -> list[KernelCheck]:
    """Every kernel in ``kernels/`` as a KernelCheck with representative
    shipped shapes and declared worst-case counts."""
    from repro.core.kv import KEY_SENTINEL
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.flash_decode import ops as fd
    from repro.kernels.fused_map import ops as fm
    from repro.kernels.moe_dispatch import ops as moe
    from repro.kernels.ssd_scan import ops as ssd
    from repro.kernels.wordcount_hash import ops as wc

    N, T = 4096, 1024
    S, V, Pn, C = 64, 512, 8, 16         # fused step: shipped engine scale
    f32, i32 = jnp.float32, jnp.int32
    return [
        KernelCheck(
            "fused_map",
            build=lambda: (fm.fused_map_step,
                           (jnp.zeros((S,), i32), jnp.zeros((S,), i32),
                            jnp.int32(1), jnp.int32(0),
                            jnp.zeros((V,), i32), jnp.ones((V,), i32),
                            jnp.full((Pn, C), KEY_SENTINEL, i32),
                            jnp.zeros((Pn, C), i32),
                            jnp.zeros((V,), i32)),
                           dict(n_procs=Pn, cap=C, block_voc=128,
                                interpret=True)),
            # int32 outputs hold per-key window totals; the engine's
            # record bound under the PR 6 saturating-combine contract
            # keeps every legitimate total well inside 2^30
            worst_count=2 ** 30,
            ops_module="repro.kernels.fused_map.ops",
            kernel_fn="repro.kernels.fused_map.kernel:fused_map_pallas"),
        KernelCheck(
            "wordcount_hash",
            build=lambda: (wc.wordcount_hist, (jnp.zeros((N,), i32),),
                           dict(vocab=512, hash_mod=8, interpret=True)),
            worst_count=N,
            ops_module="repro.kernels.wordcount_hash.ops",
            kernel_fn="repro.kernels.wordcount_hash.kernel:hist_pallas"),
        KernelCheck(
            "moe_dispatch",
            build=lambda: (moe.bucket_slots, (jnp.zeros((T,), i32),),
                           dict(n_experts=8, interpret=True)),
            worst_count=T,
            ops_module="repro.kernels.moe_dispatch.ops",
            kernel_fn="repro.kernels.moe_dispatch.kernel:"
                      "bucket_slots_pallas"),
        KernelCheck(
            "flash_attention",
            build=lambda: (fa.flash_attention,
                           (jnp.zeros((1, 128, 4, 64), f32),
                            jnp.zeros((1, 128, 2, 64), f32),
                            jnp.zeros((1, 128, 2, 64), f32)),
                           dict(causal=True, block_q=64, block_kv=64,
                                interpret=True)),
            ops_module="repro.kernels.flash_attention.ops",
            kernel_fn="repro.kernels.flash_attention.kernel:"
                      "flash_attention_pallas"),
        KernelCheck(
            "flash_decode",
            build=lambda: (fd.flash_decode,
                           (jnp.zeros((2, 4, 32), f32),
                            jnp.zeros((2, 256, 2, 32), f32),
                            jnp.zeros((2, 256, 2, 32), f32),
                            jnp.int32(100)),
                           dict(block_kv=128, interpret=True)),
            ops_module="repro.kernels.flash_decode.ops",
            kernel_fn="repro.kernels.flash_decode.kernel:"
                      "flash_decode_pallas"),
        KernelCheck(
            "ssd_scan",
            build=lambda: (ssd.ssd,
                           (jnp.zeros((1, 128, 2, 4), f32),
                            jnp.zeros((1, 128, 2), f32),
                            jnp.zeros((2,), f32),
                            jnp.zeros((1, 128, 1, 8), f32),
                            jnp.zeros((1, 128, 1, 8), f32)),
                           dict(chunk=64, interpret=True)),
            ops_module="repro.kernels.ssd_scan.ops",
            kernel_fn="repro.kernels.ssd_scan.kernel:ssd_pallas"),
    ]


# -- mutant corpus ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Mutant:
    """One seeded corpus entry. ``kind`` selects the checker:
    ``program`` -> check_program, ``kernel`` -> check_kernel,
    ``ops`` -> check_ops_module. ``fires`` is the expectation: True for
    the known-bad seed, False for its near-miss twin."""
    name: str
    rule: str
    fires: bool
    kind: str
    build: Callable = dataclasses.field(compare=False)


def _sm_handle(name, body, mesh, n_in: int = 1, replicated_in=(),
               replicated_out=(), width: int = 8) -> ProgramHandle:
    """Wrap a per-shard body into a traced-shape ProgramHandle: inputs
    are (P, width) int32 rows (rank-varying unless named in
    ``replicated_in``), output is one (1,)-shaped value per shard."""
    args = tuple(jax.ShapeDtypeStruct((int(mesh.devices.size), width),
                                      jnp.int32) for _ in range(n_in))
    specs = tuple(P("procs") for _ in range(n_in))
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=specs,
                           out_specs=P("procs")))
    return ProgramHandle(
        name=name, fn=fn, args=args,
        arg_paths=tuple(f"x{i}" for i in range(n_in)),
        out_paths=("total",), replicated_in=replicated_in,
        replicated_out=replicated_out, allowed_axes=("procs",))


def _two_axis_mesh() -> Mesh:
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("procs", "rows"))


def _spmd001(fires: bool) -> ProgramHandle:
    # psum over "rows" — a real mesh axis, but outside the engine
    # contract's allowed set ("procs",)
    axis = "rows" if fires else "procs"

    def body(x):
        return lax.psum(x.sum(), axis)[None]

    return _sm_handle(f"mutant/spmd001/{axis}", body, _two_axis_mesh())


def _spmd002(fires: bool) -> ProgramHandle:
    mesh = procs_mesh(1)

    def bad(x):
        # predicate derived from axis_index: ranks disagree on whether
        # the psum inside the branch executes -> divergence/deadlock.
        # Both branches return a rank-varying value (v + psum), so the
        # cond type-checks under shard_map's varying-axes rules
        pred = lax.axis_index("procs") % 2 == 0
        return lax.cond(pred,
                        lambda v: v + lax.psum(v, "procs"),
                        lambda v: v, x.sum())[None]

    def near(x):
        # same shape of program, but the predicate is itself a psum
        # product — replicated, so every rank takes the same branch
        pred = lax.psum(x.sum(), "procs") > 0
        return lax.cond(pred,
                        lambda v: v + lax.psum(v, "procs"),
                        lambda v: v, x.sum())[None]

    return _sm_handle(f"mutant/spmd002/{'bad' if fires else 'near'}",
                      bad if fires else near, mesh)


def _rep001(fires: bool) -> ProgramHandle:
    mesh = procs_mesh(1)

    def bad(x):
        # dropped psum: a per-rank partial sum flows into an output the
        # handle asserts replicated
        return x.sum()[None]

    def near(x):
        return lax.psum(x.sum(), "procs")[None]

    return _sm_handle(f"mutant/rep001/{'bad' if fires else 'near'}",
                      bad if fires else near, mesh,
                      replicated_out=("total",))


def _rep001_fold(fires: bool) -> ProgramHandle:
    # the elastic-fold failure mode: each rank's folded-window total
    # must be dup-summed to become the fleet total. The bad twin
    # "broadcasts" it around the ring instead — ppermute is a shuffle,
    # not a replication (every rank ends holding a *different* value),
    # which the taint rules treat as rank-varying unconditionally.
    mesh = procs_mesh(1)
    n = int(mesh.devices.size)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def bad(x):
        return lax.ppermute(x.sum()[None], "procs", perm)

    def near(x):
        return lax.psum(x.sum(), "procs")[None]

    return _sm_handle(f"mutant/rep001-fold/{'bad' if fires else 'near'}",
                      bad if fires else near, mesh,
                      replicated_out=("total",))


def _rep001_crossjob(fires: bool) -> ProgramHandle:
    # the cross-job cursor failure mode: ``carry.job_work`` (executed
    # work per member slot) is asserted replicated — each rank
    # scatter-adds the repeats it executed into a local slot row, and
    # only a psum turns those partials into the fleet row. The bad twin
    # feeds the row around the ring instead: ppermute is a shuffle, not
    # a replication (every rank ends holding a *different* partial), so
    # the taint rules keep it rank-varying and REP001 fires.
    mesh = procs_mesh(1)
    n = int(mesh.devices.size)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _slot_row(x):
        slot = x[0, 0] % 2          # member slot of the claimed task
        return jnp.zeros((1, 2), jnp.int32).at[0, slot].add(x.sum())

    def bad(x):
        return lax.ppermute(_slot_row(x), "procs", perm)[0, :1]

    def near(x):
        return lax.psum(_slot_row(x), "procs")[0, :1]

    return _sm_handle(
        f"mutant/rep001-crossjob/{'bad' if fires else 'near'}",
        bad if fires else near, mesh, replicated_out=("total",))


def _rep001_coded(fires: bool) -> ProgramHandle:
    # the coded-exchange failure mode: the decoded-bucket total each
    # rank recovers from the XOR multicast is per-rank partial state —
    # only a psum turns it into the asserted-replicated fleet total.
    # The bad twin feeds the decode accumulator around the ring instead:
    # ppermute is a shuffle, not a replication (every rank ends holding
    # a *different* decoded partial), so REP001 fires.
    mesh = procs_mesh(1)
    n = int(mesh.devices.size)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def _decoded_total(x):
        # XOR a received coded row against locally-mapped side info
        dec = jnp.bitwise_xor(x[0], x[-1])
        return dec.sum()

    def bad(x):
        return lax.ppermute(_decoded_total(x)[None], "procs", perm)

    def near(x):
        return lax.psum(_decoded_total(x), "procs")[None]

    return _sm_handle(
        f"mutant/rep001-coded/{'bad' if fires else 'near'}",
        bad if fires else near, mesh, replicated_out=("total",))


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _pal001(fires: bool) -> KernelCheck:
    index_map = (lambda i: (i + 1, 0)) if fires else (lambda i: (i, 0))

    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            grid=(8,),
            in_specs=[pl.BlockSpec((1, 128), index_map)],
            out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
            interpret=True)(x)

    return KernelCheck(
        f"mutant/pal001/{'bad' if fires else 'near'}",
        build=lambda: (fn, (jnp.zeros((8, 128), jnp.float32),), {}),
        worst_count=None)


def _pal001_fused(fires: bool) -> KernelCheck:
    # the fused_map failure mode: a sequential grid streams (vocab,)
    # table tiles while record-domain operands ride along as full
    # blocks; the bad twin's tile index map is off by one, so the last
    # grid step reads a tile past the padded table
    tile_map = (lambda j: (j + 1,)) if fires else (lambda j: (j,))

    def kernel(t_ref, r_ref, o_ref):
        o_ref[...] = t_ref[...] + r_ref[0]

    def fn(table, recs):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((512,), jnp.int32),
            grid=(8,),
            in_specs=[pl.BlockSpec((64,), tile_map),
                      pl.BlockSpec((16,), lambda j: (0,))],
            out_specs=pl.BlockSpec((64,), lambda j: (j,)),
            interpret=True)(table, recs)

    return KernelCheck(
        f"mutant/pal001-fused/{'bad' if fires else 'near'}",
        build=lambda: (fn, (jnp.zeros((512,), jnp.int32),
                            jnp.zeros((16,), jnp.int32)), {}),
        worst_count=10 ** 6)


def _pal002(fires: bool) -> KernelCheck:
    def fn(x):
        return pl.pallas_call(
            _copy_kernel,
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32),
            grid=(8,),
            in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
            interpret=True)(x)

    # 2^40 synthetic records cannot fit an int32 accumulator; 10^6 can
    worst = 2 ** 40 if fires else 10 ** 6
    return KernelCheck(
        f"mutant/pal002/{'bad' if fires else 'near'}",
        build=lambda: (fn, (jnp.zeros((8, 128), jnp.int32),), {}),
        worst_count=worst)


def _pal003(fires: bool):
    import types

    from repro.kernels.backend import default_interpret
    mod = types.ModuleType("mutant_ops")
    if fires:
        mod._on_tpu = lambda: False        # private policy copy

        def wrapper(x, interpret: bool = True):    # wrong default too
            return x
    else:
        mod.default_interpret = default_interpret

        def wrapper(x, interpret: bool | None = None):
            return x
    wrapper.__module__ = mod.__name__   # "defined in" the fake module
    mod.wrapper = wrapper
    return mod


MUTANTS = (
    Mutant("spmd001-bad", "SPMD001", True, "program",
           lambda: _spmd001(True)),
    Mutant("spmd001-near", "SPMD001", False, "program",
           lambda: _spmd001(False)),
    Mutant("spmd002-bad", "SPMD002", True, "program",
           lambda: _spmd002(True)),
    Mutant("spmd002-near", "SPMD002", False, "program",
           lambda: _spmd002(False)),
    Mutant("rep001-bad", "REP001", True, "program",
           lambda: _rep001(True)),
    Mutant("rep001-near", "REP001", False, "program",
           lambda: _rep001(False)),
    Mutant("rep001-fold-bad", "REP001", True, "program",
           lambda: _rep001_fold(True)),
    Mutant("rep001-fold-near", "REP001", False, "program",
           lambda: _rep001_fold(False)),
    Mutant("rep001-crossjob-bad", "REP001", True, "program",
           lambda: _rep001_crossjob(True)),
    Mutant("rep001-crossjob-near", "REP001", False, "program",
           lambda: _rep001_crossjob(False)),
    Mutant("rep001-coded-bad", "REP001", True, "program",
           lambda: _rep001_coded(True)),
    Mutant("rep001-coded-near", "REP001", False, "program",
           lambda: _rep001_coded(False)),
    Mutant("pal001-bad", "PAL001", True, "kernel",
           lambda: _pal001(True)),
    Mutant("pal001-near", "PAL001", False, "kernel",
           lambda: _pal001(False)),
    Mutant("pal001-fused-bad", "PAL001", True, "kernel",
           lambda: _pal001_fused(True)),
    Mutant("pal001-fused-near", "PAL001", False, "kernel",
           lambda: _pal001_fused(False)),
    Mutant("pal002-bad", "PAL002", True, "kernel",
           lambda: _pal002(True)),
    Mutant("pal002-near", "PAL002", False, "kernel",
           lambda: _pal002(False)),
    Mutant("pal003-bad", "PAL003", True, "ops",
           lambda: _pal003(True)),
    Mutant("pal003-near", "PAL003", False, "ops",
           lambda: _pal003(False)),
)


def run_mutant(mutant: Mutant) -> list:
    """Run the matching checker over one mutant; returns its findings."""
    from repro.analysis import rules
    built = mutant.build()
    if mutant.kind == "program":
        return rules.check_program(built)
    if mutant.kind == "kernel":
        return rules.check_kernel(built)
    if mutant.kind == "ops":
        return rules.check_ops_module(built, mutant.name)
    raise ValueError(f"unknown mutant kind {mutant.kind!r}")
