"""The job path's spans (``repro.core.obs``) and the phase scopes of the
engine programs, read back from compiled HLO (``launch.hlo_stats``)."""
import numpy as np
import pytest

from repro.core import JobConfig, WordCount, obs, submit
from repro.core.job import WALL_SPANS
from repro.launch.hlo_stats import op_scopes

RESULT_SPANS = ("mr.finish", "mr.result.wait", "mr.result.fetch",
                "mr.result.records")


def _tokens(n=1 << 13, vocab=200, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def _cfg(**kw):
    return JobConfig(usecase=WordCount(vocab=256), backend="1s",
                     task_size=256, push_cap=128, n_procs=1, **kw)


def _scopes_with(scopes: dict, phase: str) -> list:
    return [op for op, path in scopes.items() if phase in path.split("/")]


@pytest.mark.parametrize("segment", [4, 0], ids=["segmented", "oneshot"])
def test_a_job_keeps_one_wait_and_one_dispatch_per_segment(segment):
    with submit(_cfg(segment=segment), _tokens()) as h:
        if segment:
            while h.step():
                pass
        res = h.result()
    tr = obs.recent(1)[0]
    assert tr is h.trace
    n = h.feed.stats.segments_built
    assert n == (8 if segment else 1)
    assert tr.count("mr.feed.wait") == tr.count("mr.segment.dispatch") == n
    assert all(tr.count(s) == 1 for s in RESULT_SPANS)
    assert tr.count("mr.partition.sample") == 0     # hash: no pre-pass
    # wall_time is the covering spans, the records dict left out
    assert res.wall_time == pytest.approx(tr.seconds(*WALL_SPANS))
    assert res.wall_time == pytest.approx(h.wall_time)
    assert 0 < res.wall_time < tr.seconds(*WALL_SPANS,
                                          "mr.result.records")


def test_sampled_partitioner_pre_pass_counts_into_wall_time():
    with submit(_cfg(segment=4, partitioner="sampled"), _tokens()) as h:
        res = h.result()
    assert h.trace.count("mr.partition.sample") == 1
    assert res.wall_time == pytest.approx(h.trace.seconds(*WALL_SPANS))
    assert h.trace.ns("mr.partition.sample") > 0


def test_a_span_adds_a_count_and_its_time():
    tr = obs.JobTrace()
    for _ in range(3):
        with obs.span("a", tr):
            pass
    with obs.span("b"):                  # annotation only, no store entry
        pass
    assert tr.count("a") == 3 and tr.ns("a") > 0
    assert tr.count("b") == 0 and tr.seconds("b") == 0
    assert tr.seconds("a", "b") == tr.ns("a") * 1e-9
    with pytest.raises(ValueError):
        with obs.span("c", tr):
            raise ValueError("the body failed")
    assert tr.count("c") == 0


def test_recent_keeps_the_last_finished_traces_in_order():
    made = [obs.JobTrace() for _ in range(70)]
    for t in made:
        obs.finished(t)
    assert obs.recent(3) == made[-3:]
    assert obs.recent(0) == []
    assert len(obs.recent(1000)) == 64             # bounded


def test_op_scopes_reads_the_metadata_of_every_instruction():
    text = """HloModule jit_mr_segment, entry_computation_layout={()}
%fused_computation (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(mr_segment)/fold/add" source_file="x.py" source_line=3}
}
ENTRY %main (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %sort.0 = s32[8]{0} sort(%p), dimensions={0}, metadata={op_name="jit(mr_segment)/local_reduce/jit(sort)/sort"}
  %copy.3 = s32[8]{0} copy(%sort.0)
  ROOT %fusion.2 = s32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(mr_segment)/fold/add"}
}
"""
    assert op_scopes(text) == {
        "param_0": "", "add.1": "jit(mr_segment)/fold/add",
        "p": "", "sort.0": "jit(mr_segment)/local_reduce/jit(sort)/sort",
        "copy.3": "", "fusion.2": "jit(mr_segment)/fold/add"}


def test_the_segment_program_that_ran_names_its_phases():
    with submit(_cfg(segment=4), _tokens()) as h:
        h.result()
    scopes = h.trace.op_scopes("segment")
    assert scopes is h.trace.op_scopes("segment")            # memoized
    for phase in ("map", "local_reduce", "route", "fold"):
        ops = _scopes_with(scopes, phase)
        assert ops, phase
        assert any(scopes[op].startswith("jit(mr_segment)/") for op in ops)


def test_the_three_programs_are_named_and_finish_names_its_phases():
    import jax
    with submit(_cfg(segment=4), _tokens()) as h:
        h.result()
    init, seg, fin = h._seg_fns
    carry = jax.eval_shape(init)
    text = fin.lower(carry).compile().as_text()
    assert text.startswith("HloModule jit_mr_finish")
    assert init.lower().compile().as_text().startswith(
        "HloModule jit_mr_init")
    scopes = op_scopes(text)
    assert _scopes_with(scopes, "combine") and _scopes_with(scopes, "tree")


def test_the_stealing_segment_names_its_claim_and_fetch():
    with submit(_cfg(segment=4, stealing=True), _tokens()) as h:
        res = h.result()
    assert res.wall_time == pytest.approx(h.trace.seconds(*WALL_SPANS))
    scopes = h.trace.op_scopes("segment")
    for phase in ("claim", "fetch", "local_reduce", "fold"):
        assert _scopes_with(scopes, phase), phase
