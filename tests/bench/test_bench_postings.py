"""The Inverted-Index cell ``ii-wiki-1chip``: its parts found by name, the
plain reference against a brute-force count, the control reading above
the limit of ``correct``, a planted fault read as wrong, and the readers
of the log compaction on synthetic runs."""
import dataclasses
import gzip
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import cells, control, run, trace
from bench.trace import TraceMismatch
from repro.core import obs

CELL = "ii-wiki-1chip"
SPEC = cells.load_benchmark()


def _tiny(tokens=1 << 15, doc_len=672, **job):
    """The cell at a CPU test's size: fewer tokens, smaller tasks, the
    vocabulary and the document length as given."""
    cell = cells.load_cell(CELL)
    uc = cell.config["usecase"]
    cfg = dict(cell.config, tokens_per_job=tokens,
               usecase=dict(uc, args=dict(uc["args"], doc_len=doc_len)),
               job=dict(cell.config["job"],
                        **{"task_size": 1024, "push_cap": 256, **job}))
    return dataclasses.replace(cell, config=cfg)


def test_the_cell_loads_with_its_configuration_and_metrics():
    cell = cells.load_cell(CELL)
    assert cell.chips == 1 and cell.config_name == (
        "invertedindex-puma-wiki-1chip")
    assert cell.usecase_name == "postings"
    assert cell.config["usecase"]["class"] == "PostingsIndex"
    assert cell.config["job"]["backend"] == "1s"
    assert cell.config["job"]["n_procs"] == 1
    assert [m.name for m in cell.end_to_end] == ["tokens_per_s", "setup_s"]
    unscoped = [m["name"] for m in SPEC["per_layer"] if "workloads" not in m]
    assert [m.name for m in cell.per_layer] == unscoped + [
        "compact_ms_per_job", "compact_roofline"]
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m.name))


def _brute(tokens, doc_len):
    want = {}
    for p, w in enumerate(np.asarray(tokens).tolist()):
        key = (w << 32) | (p // doc_len)
        want[key] = want.get(key, 0) + 1
    return want


@pytest.mark.parametrize("doc_len", [1, 7, 672])
def test_reference_equals_a_brute_force_count(doc_len):
    oracle = cells.oracle("postings")
    rng = np.random.default_rng(doc_len)
    tokens = rng.integers(0, 1 << 22, 3000).astype(np.int32)
    tokens[::5] = tokens[0]
    got = oracle.reference(tokens, vocab=1 << 22, doc_len=doc_len)
    assert got == _brute(tokens, doc_len)
    assert list(got) == sorted(got)


def test_control_wraps_the_high_word_ids():
    oracle = cells.oracle("postings")
    # 4 documents: two bits of document, so word ids from 2^30 wrap
    tokens = np.array([3, 1 << 30, 3, (1 << 30) + 3, 5, 5, 7, 3],
                      np.int32)
    got = oracle.control(tokens, vocab=1 << 31, doc_len=2)
    want = oracle.reference(tokens, vocab=1 << 31, doc_len=2)
    assert run.records_wrong(got, want) > 0
    low = tokens.copy()
    low[low >= 1 << 30] = 9
    assert (oracle.control(low, vocab=1 << 31, doc_len=2)
            == oracle.reference(low, vocab=1 << 31, doc_len=2))


def test_control_fails_where_the_program_passes():
    # 2^15 tokens in 8-token documents: 4,096 documents, so word ids from
    # 2^20 wrap in the control
    cell = _tiny(doc_len=8)
    assert control.control_reading(cell, 2 ** 31 + 11) > 0
    assert control.program_reading(cell, 2 ** 31 + 11) == 0


def test_engine_equals_reference_on_the_cpu(cpu_devices):
    line, checks = run.run_cell(_tiny(), cpu_devices, 2 ** 33 + 5, 0.2,
                                False, log=lambda msg: None)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["checks"]["records_wrong"] == {"value": 0, "limit": 0}
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}


def test_a_step_that_drops_the_document_lane(monkeypatch, cpu_devices):
    import jax.numpy as jnp

    from repro.core import onesided
    from repro.core.kv import KEY_SENTINEL
    from repro.core.registry import get_backend
    real = onesided._log_step

    def dropped(spec, map_fn, carry, xs):
        def no_document(*args):
            (words, docs), vals = map_fn(*args)
            return (words, jnp.where(words == KEY_SENTINEL, docs, 0)), vals
        return real(spec, no_document, carry, xs)

    programs = get_backend("1s")._programs
    programs.clear()
    monkeypatch.setattr(onesided, "_log_step", dropped)
    try:
        line, _ = run.run_cell(_tiny(), cpu_devices, 2 ** 31 + 29, 0.1,
                               False, log=lambda msg: None)
    finally:
        programs.clear()
    assert line["correct"] is False
    assert line["checks"]["records_wrong"]["value"] > 0


FINISH_OPS = {"sort.1": "jit(mr_finish)/compact/sort",
              "fusion.2": "jit(mr_finish)/compact/scatter",
              "fusion.3": "jit(mr_finish)/tree/select_n",
              "copy.4": ""}


def _finish_run(monkeypatch, name="jit_mr_finish(9)", scopes=FINISH_OPS):
    def dev(scale):
        return SimpleNamespace(op_self_ns={
            "finish:sort.1": 30_000_000 * scale,
            "finish:fusion.2": 10_000_000 * scale,
            "finish:fusion.3": 1_000_000 * scale, "finish:copy.4": 7,
            "segment:sort.1": 5})
    summary = SimpleNamespace(per_device=[dev(1), dev(3)],
                              program_names={"finish": name})
    job = SimpleNamespace(op_scopes=lambda role: scopes)
    monkeypatch.setattr(obs, "recent", lambda n: [job] * n)
    return SimpleNamespace(trace=summary, jobs=[None, None], chips=1,
                           tokens_per_job=1 << 22, device_kind="TPU v5 lite")


def test_compact_readers_on_a_synthetic_run(monkeypatch):
    # two jobs; devices of 1x and 3x: mean 2x (30 + 10) ms over 2 jobs
    run_ = _finish_run(monkeypatch)
    ms = cells.metric_reader("compact_ms_per_job")(run_)
    assert ms == pytest.approx(40.0)
    share = cells.metric_reader("compact_roofline")(run_)
    least = 24 * (1 << 22) / 819e9
    assert share == pytest.approx(100 * least / 0.040)


def test_compact_readers_refuse_what_they_cannot_name(monkeypatch):
    read = cells.metric_reader("compact_ms_per_job")
    with pytest.raises(TraceMismatch, match="jit_mr_finish"):
        read(_finish_run(monkeypatch, name="jit__lambda(3)"))
    lacking = {k: v for k, v in FINISH_OPS.items() if k != "copy.4"}
    with pytest.raises(TraceMismatch, match="copy.4"):
        read(_finish_run(monkeypatch, scopes=lacking))


def test_compact_readers_read_nothing_without_a_compaction(monkeypatch):
    # a one-lane finish program has no compact scope; a program that
    # notes no finish program has no map to read
    dense = {k: v.replace("compact", "combine")
             for k, v in FINISH_OPS.items()}
    for m in ("compact_ms_per_job", "compact_roofline"):
        assert cells.metric_reader(m)(
            _finish_run(monkeypatch, scopes=dense)) is None

    def unnoted(role):
        raise KeyError(role)
    job = SimpleNamespace(op_scopes=unnoted)
    monkeypatch.setattr(obs, "recent", lambda n: [job] * n)
    assert cells.metric_reader("compact_ms_per_job")(None) is None


def _fixture(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures", name)
    with gzip.open(path, "rb") as f:
        return f.read()


def test_compact_readers_on_the_recorded_tpu_trace(monkeypatch):
    # two Inverted-Index jobs of 2^16 tokens at vocab 2^12, task 4096,
    # segment 8, on one TPU v5e, and the op -> scope map of their finish
    # program
    summary = trace.reduce_profile(ProfileData.from_serialized_xspace(
        _fixture("tpu1_postings_compact.xplane.pb.gz")), [0], [2, 2])
    scopes = json.loads(_fixture("tpu1_postings_compact.op_scopes.json.gz"))
    assert summary.program_names["finish"].startswith("jit_mr_finish(")
    job = SimpleNamespace(op_scopes=lambda role: scopes)
    monkeypatch.setattr(obs, "recent", lambda n: [job] * n)
    run_ = SimpleNamespace(trace=summary, jobs=[None, None], chips=1,
                           tokens_per_job=1 << 16, device_kind="TPU v5 lite")
    ms = cells.metric_reader("compact_ms_per_job")(run_)
    assert 0 < ms < summary.program_s("finish") * 1e3 / 2
    assert 0 < cells.metric_reader("compact_roofline")(run_) <= 100
