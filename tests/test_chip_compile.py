"""Compile rehearsals for the TPU v5e: the engine programs ``submit``
accepts there, at the widths ``chip_smoke.py`` runs, compiled by the TPU
compiler for a described ``v5e:2x2`` topology with no chip attached.

Nothing runs: a compile that passes here says the program lowers and
fits, not what it computes or how fast. The topology is described only
inside the module fixture, so every xdist worker collects the same tests
and only the worker given this file loads the TPU library.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import Histogram, PostingsIndex, WordCount, get_backend
from repro.core.registry import JobSpec
from repro.core.usecase import as_map_fn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke_widths():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


W = _smoke_widths()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described compile is written to the persistent cache but cannot
    # be read back without a chip: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def meshes(topo):
    return {1: Mesh(np.array(topo.devices[:1]), ("procs",)),
            4: Mesh(np.array(topo.devices[:4]), ("procs",))}


def _compile_triple(backend: str, mesh: Mesh, *,
                    programs=("init", "segment", "finish"), usecase=None,
                    **spec_kw):
    P = int(mesh.devices.size)
    usecase = usecase or WordCount(vocab=W.VOCAB)
    spec_kw.setdefault("vocab", usecase.window)
    spec = JobSpec(task_size=W.TASK_SIZE, push_cap=W.PUSH_CAP, n_procs=P,
                   segment=W.SEGMENT, **spec_kw)
    init, seg, fin = get_backend(backend).make_segment_fns(
        spec, as_map_fn(usecase), mesh)
    sh = NamedSharding(mesh, PartitionSpec("procs"))

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    carry = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                         jax.eval_shape(init))
    n = W.SEGMENT
    args = {"init": (init, ()),
            "segment": (seg, (carry, sds((P, n, W.TASK_SIZE)),
                              sds((P, n)), sds((P, n)))),
            "finish": (fin, (carry,))}
    out = {}
    for name in programs:
        fn, a = args[name]
        compiled = fn.lower(*a).compile()
        out[name] = compiled
        assert compiled.memory_analysis() is not None
    return out


@pytest.mark.parametrize("backend", ["1s", "2s"])
def test_engine_compiles_on_one_chip(meshes, backend):
    out = _compile_triple(backend, meshes[1])
    # the window carry is three (vocab,) int32 rows: table, owner map,
    # owner split; the segment program must fit one v5e's 16 GB
    seg = out["segment"].memory_analysis()
    assert seg.argument_size_in_bytes >= 3 * 4 * W.VOCAB
    assert seg.argument_size_in_bytes + seg.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("backend", ["1s", "2s"])
def test_engine_compiles_on_four_chips(meshes, backend):
    out = _compile_triple(backend, meshes[4])
    text = out["segment"].as_text()
    assert "all-to-all" in text          # the shuffle crosses chips
    fin = out["finish"].memory_analysis()
    assert fin.temp_size_in_bytes < 16e9


def test_stealing_segment_compiles_on_four_chips(meshes):
    out = _compile_triple("1s", meshes[4], stealing=True,
                          programs=("segment",))
    assert "all-to-all" in out["segment"].as_text()


_POSTINGS = PostingsIndex(vocab=W.VOCAB, doc_len=672)
_COSLOTS = dict(vocab=2 * W.VOCAB, coslots=2, costride=1 << 16)


@pytest.mark.parametrize("P,usecase,kw,programs", [
    # the Histogram cell's window: five keys
    (1, Histogram(vocab=5, n_bins=5), {}, ("init", "segment", "finish")),
    # the Inverted-Index cell's finish program (the log's compaction) at
    # a log of one step: the compiler's time for its sort grows faster
    # than the log (minutes at the cell's 2^22 slots)
    (1, _POSTINGS, dict(key_lanes=2, log_steps=1), ("finish",)),
    # the coded finish reads no code_rate: it is the plain P=4 finish
    (4, None, dict(code_rate=2), ("segment",)),
    (4, None, dict(code_rate=2, stealing=True), ("segment",)),
    (1, None, _COSLOTS, ("init", "segment", "finish")),
    (4, None, dict(_COSLOTS, stealing=True), ("segment",)),
    # two-lane keys across chips, at a log of one segment's steps
    (4, _POSTINGS, dict(key_lanes=2, log_steps=W.SEGMENT), ("segment",)),
    (4, _POSTINGS, dict(key_lanes=2, log_steps=W.SEGMENT, stealing=True),
     ("segment",)),
], ids=["histogram-p1", "two-lane-finish-p1", "coded-p4", "coded-steal-p4",
        "cosched-p1", "cosched-steal-p4", "two-lane-p4", "two-lane-steal-p4"])
def test_every_accepted_program_compiles(meshes, P, usecase, kw, programs):
    """Each program ``submit`` accepts on the TPU lowers and fits one
    v5e: the Histogram and Inverted-Index cells' programs, and the coded,
    co-scheduled and two-lane engines at P=1 and P=4."""
    out = _compile_triple("1s", meshes[P], usecase=usecase,
                          programs=programs, **kw)
    for name, compiled in out.items():
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9, \
            name
    if P > 1 and "segment" in out:
        assert "all-to-all" in out["segment"].as_text()


def test_segment_program_names_its_phases_for_the_tpu(meshes):
    from repro.launch.hlo_stats import op_scopes
    text = _compile_triple("1s", meshes[1],
                           programs=("segment",))["segment"].as_text()
    assert text.startswith("HloModule jit_mr_segment")
    scopes = op_scopes(text)
    for phase in ("local_reduce", "route", "fold"):
        assert any(phase in path.split("/") for path in scopes.values()), \
            phase


def test_two_lane_segment_program_compiles_for_one_chip(meshes):
    # the Inverted-Index cell's segment program: (word, document) keys at
    # vocab 2^22 appending to a log of one slot per token of a 2^22-token
    # job, 1,024 steps of 4,096 tokens
    from repro.core import PostingsIndex
    from repro.launch.hlo_stats import op_scopes
    mesh = meshes[1]
    spec = JobSpec(vocab=1 << 22, task_size=4096, push_cap=1024, n_procs=1,
                   segment=8, key_lanes=2, log_steps=1024)
    init, seg, _ = get_backend("1s").make_segment_fns(
        spec, as_map_fn(PostingsIndex(vocab=1 << 22, doc_len=672)), mesh)
    sh = NamedSharding(mesh, PartitionSpec("procs"))

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    carry = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                         jax.eval_shape(init))
    compiled = seg.lower(carry, sds((1, 8, 4096)), sds((1, 8)),
                         sds((1, 8))).compile()
    # the log (three int32 lanes of 2^22 slots) and the two owner maps
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * (3 * (1 << 22) + 2 * (1 << 22))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    scopes = op_scopes(compiled.as_text())
    for phase in ("local_reduce", "route", "log_append"):
        assert any(phase in path.split("/") for path in scopes.values()), \
            phase
