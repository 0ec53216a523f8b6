"""Unit tests for the unified Job API (single real device, n_procs=1).

Covers the backend registry (resolution, registration, clear errors),
the submit()/JobHandle lifecycle (oneshot vs segmented equivalence,
step/cursor semantics, structured JobResult), and oracle equality for
every built-in use-case on both built-in backends. The 8-device variants
live in tests/test_engine.py (marked slow).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (Backend, Histogram, InvertedIndex, JobConfig,
                        UnknownBackendError, WordCount, available_backends,
                        get_backend, histogram_oracle, inverted_index_oracle,
                        register_backend, submit, wordcount_oracle)

VOCAB, N, TASK = 200, 8192, 512


@pytest.fixture(scope="module")
def tokens():
    rng = np.random.default_rng(0)
    return rng.integers(0, VOCAB, size=N).astype(np.int32)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_builtin_backends_resolve():
    assert {"1s", "2s"} <= set(available_backends())
    for name in ("1s", "2s"):
        b = get_backend(name)
        assert isinstance(b, Backend)
        assert b.name == name
        assert get_backend(name) is b          # singleton (jit caches)


def test_unknown_backend_clear_error():
    with pytest.raises(UnknownBackendError, match=r"nope.*1s.*2s"):
        get_backend("nope")


def test_register_backend_decorator():
    @register_backend("test-dummy")
    class Dummy:
        def make_segment_fns(self, spec, map_fn, mesh):
            raise NotImplementedError

    try:
        assert get_backend("test-dummy").name == "test-dummy"
        assert "test-dummy" in available_backends()
    finally:
        from repro.core import registry
        registry._REGISTRY.pop("test-dummy", None)
        registry._INSTANCES.pop("test-dummy", None)


def test_submit_rejects_unknown_backend(tokens):
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend="3s",
                    n_procs=1)
    with pytest.raises(UnknownBackendError):
        submit(cfg, tokens)


def test_fused_map_is_accepted_as_false_only():
    """Job files written for the removed fused map kernel name
    ``fused_map: false``: they still build a config, and True is
    refused instead of being ignored."""
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), fused_map=False)
    assert cfg == JobConfig(usecase=WordCount(vocab=VOCAB))
    with pytest.raises(ValueError, match="fused_map"):
        JobConfig(usecase=WordCount(vocab=VOCAB), fused_map=True)


# ---------------------------------------------------------------------------
# JobHandle lifecycle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["1s", "2s"])
def test_oneshot_result_structured(tokens, backend):
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend=backend,
                    task_size=TASK, push_cap=256, n_procs=1)
    res = submit(cfg, tokens).result()
    assert res.records == wordcount_oracle(tokens, VOCAB)
    assert res.output == res.records           # WordCount has no finalize
    assert res.backend == backend
    assert res.n_tasks == N // TASK
    assert res.tasks_per_rank.sum() == res.n_tasks
    assert res.work_per_rank.sum() == res.n_tasks  # all repeats == 1
    assert res.imbalance == 1.0
    assert res.wall_time > 0


@pytest.mark.parametrize("backend", ["1s", "2s"])
def test_segmented_equals_oneshot(tokens, backend):
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend=backend,
                    task_size=TASK, push_cap=256, n_procs=1)
    oneshot = submit(cfg, tokens).result()
    handle = submit(dataclasses.replace(cfg, segment=3), tokens)
    steps = 0
    while handle.step():
        steps += 1
    assert steps == (N // TASK + 2) // 3 - 1   # last step returns False
    res = handle.result()
    assert res.records == oneshot.records
    assert (res.keys == oneshot.keys).all()


def test_step_requires_segmented(tokens):
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend="1s",
                    task_size=TASK, push_cap=256, n_procs=1)
    with pytest.raises(RuntimeError, match="segment"):
        submit(cfg, tokens).step()


def test_result_is_cached(tokens):
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend="1s",
                    task_size=TASK, push_cap=256, n_procs=1)
    h = submit(cfg, tokens)
    assert not h.done
    r1 = h.result()
    assert h.done
    assert h.result() is r1
    assert not h.step()                         # done job refuses to step


# ---------------------------------------------------------------------------
# use-case oracle equality (both backends, oneshot + segmented)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["1s", "2s"])
@pytest.mark.parametrize("segment", [0, 4])
def test_histogram_oracle(tokens, backend, segment):
    uc = Histogram(vocab=VOCAB, n_bins=16)
    cfg = JobConfig(usecase=uc, backend=backend, task_size=TASK,
                    push_cap=TASK, n_procs=1, segment=segment)
    res = submit(cfg, tokens).result()
    np.testing.assert_array_equal(res.output,
                                  histogram_oracle(tokens, VOCAB, 16))


@pytest.mark.parametrize("backend", ["1s", "2s"])
@pytest.mark.parametrize("segment", [0, 4])
def test_inverted_index_oracle(tokens, backend, segment):
    queries = (3, 17, 42, 199)
    uc = InvertedIndex(queries=queries, n_docs=4, tasks_per_doc=4)
    cfg = JobConfig(usecase=uc, backend=backend, task_size=TASK,
                    push_cap=TASK, n_procs=1, segment=segment)
    res = submit(cfg, tokens).result()
    assert res.output == inverted_index_oracle(tokens, queries, TASK, 4, 4)


@pytest.mark.parametrize("backend", ["1s", "2s"])
def test_combine_capacity_consistent_across_modes(tokens, backend):
    """A non-default Combine window must produce identical records in
    oneshot and segmented mode (it used to be honored only by the 1s
    oneshot path). VOCAB=200 keys all occur, so 256 is the smallest
    power-of-two capacity that does NOT overflow — see the overflow
    tests below for the undersized case, which now raises."""
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend=backend,
                    task_size=TASK, push_cap=256, n_procs=1,
                    combine_capacity=256)
    oneshot = submit(cfg, tokens).result()
    seg = submit(dataclasses.replace(cfg, segment=4), tokens).result()
    assert oneshot.records == seg.records
    assert oneshot.combine_overflow == 0
    assert oneshot.records == wordcount_oracle(tokens, VOCAB)


@pytest.mark.parametrize("backend", ["1s", "2s"])
def test_combine_overflow_raises_not_silent(tokens, backend):
    """THE headline bugfix: an undersized combine_capacity used to
    *silently drop* every key past the capacity — result() returned
    wrong counts with no signal. It must now raise, carrying the
    overflow count and the (wrong) partial result for inspection."""
    from repro.core import CombineOverflowError
    oracle = wordcount_oracle(tokens, VOCAB)
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend=backend,
                    task_size=TASK, push_cap=256, n_procs=1,
                    combine_capacity=128)
    h = submit(cfg, tokens)
    with pytest.raises(CombineOverflowError, match="combine_capacity"):
        h.result()
    # the attached partial result is the pre-fix behavior: provably wrong
    try:
        h.result()                          # raises again — never silent
    except CombineOverflowError as e:
        assert e.result.combine_overflow > 0
        assert e.result.records != oracle   # pre-fix counts WERE wrong
        assert sum(e.result.records.values()) < sum(oracle.values())
        # exactly the dropped tail is accounted for
        assert (len(oracle) - len(e.result.records)
                == e.result.combine_overflow)
    assert h.feed._closed                   # stream was still torn down


def test_result_closes_feed_on_engine_error(tokens):
    """A raising segment/finish fn must not leak the feed's prefetch
    thread: result() closes the feed on every exit path."""
    @dataclasses.dataclass(frozen=True)
    class Broken:
        vocab: int

        @property
        def window(self):
            return self.vocab

        def map_emit(self, toks, task_id):
            raise ValueError("boom at trace time")

    cfg = JobConfig(usecase=Broken(vocab=VOCAB), backend="1s",
                    task_size=TASK, push_cap=256, n_procs=1)
    h = submit(cfg, tokens)
    with pytest.raises(ValueError, match="boom"):
        h.result()
    assert h.feed._closed                   # used to stay open forever


def test_jobhandle_context_manager(tokens):
    """``with submit(...) as h`` releases the feed even when the body
    abandons the job mid-stream (no result() ever called)."""
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend="1s",
                    task_size=TASK, push_cap=256, n_procs=1, segment=2)
    with submit(cfg, tokens) as h:
        h.step()
        assert not h.feed._closed
    assert h.feed._closed
    # and the normal full-lifecycle use still works inside the block
    with submit(cfg, tokens) as h2:
        assert h2.result().records == wordcount_oracle(tokens, VOCAB)
    assert h2.feed._closed
    h2.close()                              # idempotent


def test_custom_usecase_with_local_reduce_combiner(tokens):
    """A user-defined use-case exercising the optional combiner hook."""
    import jax.numpy as jnp
    from repro.core.kv import KEY_SENTINEL, local_reduce

    @dataclasses.dataclass(frozen=True)
    class EvenCount:
        vocab: int

        @property
        def window(self):
            return self.vocab

        def map_emit(self, toks, task_id):
            valid = (toks != KEY_SENTINEL) & (toks % 2 == 0)
            keys = jnp.where(valid, toks, KEY_SENTINEL)
            return keys, jnp.where(valid, 1, 0).astype(jnp.int32)

        def local_reduce(self, keys, vals):
            return local_reduce(keys, vals, keys.shape[0])[:2]

    cfg = JobConfig(usecase=EvenCount(vocab=VOCAB), backend="1s",
                    task_size=TASK, push_cap=256, n_procs=1)
    res = submit(cfg, tokens).result()
    evens = tokens[tokens % 2 == 0]
    assert res.records == wordcount_oracle(evens, VOCAB)


# ---------------------------------------------------------------------------
# deprecated shim is gone (was kept one release, removed in PR 9)
# ---------------------------------------------------------------------------

def test_deprecated_shim_removed():
    """The class-based MapReduceJob shim and its lazy __getattr__ hook
    were removed after their one-release migration window: the old names
    must fail loudly (AttributeError / ImportError), not half-work."""
    import importlib.util
    import repro.core
    with pytest.raises(AttributeError, match="MapReduceJob"):
        repro.core.MapReduceJob
    assert importlib.util.find_spec("repro.core.api") is None
    assert importlib.util.find_spec("repro.core.wordcount") is None
    assert "MapReduceJob" not in dir(repro.core)


def test_migrated_wordcount_replaces_shim(tokens):
    """The submit() one-liner the shim's migration table pointed at —
    the exact replacement for the removed subclass-style WordCount."""
    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend="1s",
                    task_size=TASK, push_cap=256, n_procs=1)
    res = submit(cfg, tokens).result()
    assert res.records == wordcount_oracle(tokens, VOCAB)


class _FakeChip:
    platform, device_kind = "tpu", "TPU v5 lite"


@pytest.mark.parametrize("devices,hint", [
    (None, "xla_force_host_platform_device_count"),
    ([_FakeChip()], "run on a host with 4 chips"),
])
def test_mesh_shortage_names_the_platform(monkeypatch, devices, hint):
    """Too few devices: a CPU host is told to force host devices, a chip
    host how many chips it has."""
    import jax

    from repro.distributed import mesh
    if devices is not None:
        monkeypatch.setattr(jax, "devices", lambda: devices)
    n = len(jax.devices()) + 3 if devices is None else 4
    with pytest.raises(RuntimeError, match=hint) as err:
        mesh.abstract_devices(n)
    if devices is not None:
        assert "xla_force" not in str(err.value)
        assert "1 tpu device(s) (TPU v5 lite)" in str(err.value)
