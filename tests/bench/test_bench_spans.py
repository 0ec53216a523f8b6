"""The readers of the program's spans and phase scopes (``bench/spans.py``,
``bench/metrics/``): the spans on a CPU profile, each reader on synthetic
runs, and the phase readers on a trace recorded on a TPU v5e."""
import gzip
import json
import os
import sys
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from bench import cells, run, spans
from bench.trace import TraceMismatch
from repro.core import obs

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
HOST = {"feed_wait_ms_per_job": "mr.feed.wait",
        "result_fetch_ms_per_job": "mr.result.fetch",
        "result_records_ms_per_job": "mr.result.records"}
PHASE = {"segment_local_reduce_ms_per_mtok": "local_reduce",
         "segment_route_ms_per_mtok": "route",
         "segment_fold_ms_per_mtok": "fold"}


def _events(profile, prefix):
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name.startswith(prefix)]
    return out


def _inside(event, spans_of_kind):
    _, s, e = event
    return any(a <= s and e <= b for _, a, b in spans_of_kind)


def test_spans_land_on_the_host_plane_inside_the_bench_spans(
        tiny_cell, tmp_path):
    import jax
    cell = tiny_cell("wc-wiki-1chip", tokens=1 << 14, vocab=1 << 8,
                     task_size=256, push_cap=128, segment=16)
    s = run.set_up(cell, 2 ** 31 + 5)
    with jax.profiler.trace(str(tmp_path)):
        jobs = [run.run_job(s.job_cfg, s.source)[0] for _ in range(2)]
    from bench.trace import find_xplane
    prof = ProfileData.from_file(find_xplane(tmp_path))
    mr, bench = _events(prof, "mr."), _events(prof, "bench.")
    segments = sum(j.segments for j in jobs)
    assert segments == 8
    count = {n: sum(e[0] == n for e in mr) for n in {e[0] for e in mr}}
    assert count.pop("mr.feed.build") == segments
    assert count == {"mr.feed.wait": segments,
                     "mr.segment.dispatch": segments, "mr.finish": 2,
                     "mr.result.wait": 2, "mr.result.fetch": 2,
                     "mr.result.records": 2}
    steps = [e for e in bench if e[0] == "bench.step"]
    results = [e for e in bench if e[0] == "bench.result"]
    for e in mr:
        if e[0] in ("mr.feed.wait", "mr.segment.dispatch"):
            assert _inside(e, steps), e
        elif e[0] != "mr.feed.build":
            assert _inside(e, results), e
    # the same counts in the jobs' own traces
    for tr, j in zip(obs.recent(2), jobs):
        assert tr.count("mr.segment.dispatch") == j.segments


def _host_run(ns_per_job, segments):
    for ns, n in zip(ns_per_job, segments):
        tr = obs.JobTrace()
        for _ in range(n):
            tr.add("mr.feed.wait", ns)
            tr.add("mr.segment.dispatch", 10)
        for name in ("mr.result.fetch", "mr.result.records"):
            tr.add(name, ns * n)
        obs.finished(tr)
    return SimpleNamespace(jobs=[SimpleNamespace(segments=n)
                                 for n in segments])


@pytest.mark.parametrize("metric", sorted(HOST))
def test_host_readers_read_the_last_jobs_traces(metric):
    read = cells.metric_reader(metric)
    # 3 and 5 segments of 2 ms and 4 ms each: 6 + 20 ms over 2 jobs
    got = read(_host_run([2_000_000, 4_000_000], [3, 5]))
    assert got == pytest.approx(13.0)
    assert spans.host_ms_per_job(_host_run([1_000_000], [2]),
                                 HOST[metric]) == pytest.approx(2.0)


@pytest.mark.parametrize("metric", sorted(HOST))
def test_host_readers_refuse_traces_of_other_jobs(metric):
    run_ = _host_run([1_000_000, 1_000_000], [3, 5])
    run_.jobs[1].segments = 6
    with pytest.raises(TraceMismatch, match="dispatched"):
        cells.metric_reader(metric)(run_)


OPS = {"fusion.1": "jit(mr_segment)/while/body/local_reduce/sort",
       "fusion.2": "jit(mr_segment)/while/body/route/gather",
       "fusion.3": "jit(mr_segment)/while/body/fold/scatter-add",
       "fusion.4": "jit(mr_segment)/while/body/map/select_n",
       "copy.5": "", "while.6": "jit(mr_segment)/while"}


def _device_run(monkeypatch, name="jit_mr_segment(77)", scopes=OPS):
    def dev(scale):
        return SimpleNamespace(op_self_ns={
            "segment:fusion.1": 6_000_000 * scale,
            "segment:fusion.2": 2_000_000 * scale,
            "segment:fusion.3": 1_000_000 * scale,
            "segment:fusion.4": 500_000 * scale,
            "segment:copy.5": 100_000, "segment:while.6": 100_000,
            "init:fusion.9": 7, "finish:sort.3": 7})
    summary = SimpleNamespace(per_device=[dev(1), dev(3)],
                              program_names={"init": "jit_mr_init(1)",
                                             "segment": name,
                                             "finish": "jit_mr_finish(2)"})
    job = SimpleNamespace(op_scopes=lambda role: scopes)
    monkeypatch.setattr(obs, "recent", lambda n: [job] * n)
    return SimpleNamespace(trace=summary, jobs=[None, None],
                           tokens_per_job=1_000_000)


@pytest.mark.parametrize("metric", sorted(PHASE))
def test_phase_readers_sum_self_time_under_their_scope(monkeypatch, metric):
    # two jobs of 1 M tokens; devices of 1x and 3x: mean 2x the op times
    want = {"local_reduce": 6.0, "route": 2.0, "fold": 1.0}[PHASE[metric]]
    got = cells.metric_reader(metric)(_device_run(monkeypatch))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(PHASE))
def test_phase_readers_refuse_what_they_cannot_name(monkeypatch, metric):
    read = cells.metric_reader(metric)
    with pytest.raises(TraceMismatch, match="jit_mr_segment"):
        read(_device_run(monkeypatch, name="jit__lambda(3)"))
    lacking = {k: v for k, v in OPS.items() if k != "copy.5"}
    with pytest.raises(TraceMismatch, match="copy.5"):
        read(_device_run(monkeypatch, scopes=lacking))


def test_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    import repro.core
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)
    monkeypatch.delattr(repro.core, "obs")
    for metric in sorted(HOST) + sorted(PHASE):
        assert cells.metric_reader(metric)(None) is None


def _fixture(name):
    with gzip.open(os.path.join(FIXTURES, name), "rb") as f:
        return f.read()


def test_phase_readers_on_the_recorded_tpu_trace(monkeypatch):
    # two WordCount jobs of 2^16 tokens at vocab 2^12, task 4096, segment
    # 8, on one TPU v5e, and the op -> scope map of their segment program
    from bench import trace
    summary = trace.reduce_profile(ProfileData.from_serialized_xspace(
        _fixture("tpu1_wordcount_spans.xplane.pb.gz")), [0], [2, 2])
    scopes = json.loads(_fixture("tpu1_wordcount_spans.op_scopes.json.gz"))
    assert summary.program_names["segment"].startswith("jit_mr_segment(")
    assert summary.program_names["init"].startswith("jit_mr_init(")
    assert summary.program_names["finish"].startswith("jit_mr_finish(")
    job = SimpleNamespace(op_scopes=lambda role: scopes)
    monkeypatch.setattr(obs, "recent", lambda n: [job] * n)
    run_ = SimpleNamespace(trace=summary, jobs=[None, None],
                           tokens_per_job=1 << 16)
    got = {m: cells.metric_reader(m)(run_) for m in sorted(PHASE)}
    segment = summary.program_s("segment") * 1e3 / (2 * (1 << 16) / 1e6)
    assert all(v > 0 for v in got.values()), got
    assert sum(got.values()) < segment


STEAL_OPS = {"fusion.1": "jit(mr_segment)/while/body/claim/while/body/sort",
             "all-reduce.2": "jit(mr_segment)/while/body/claim/psum",
             "all-to-all.3": "jit(mr_segment)/while/body/fetch/all_to_all",
             "all-to-all.4": "jit(mr_segment)/while/body/push/all_to_all",
             "all-to-all.5": "jit(mr_segment)/while/body/push/all_to_all",
             "fusion.6": "jit(mr_segment)/while/body/fold/scatter-add"}


def _steal_run(monkeypatch, scopes=STEAL_OPS):
    ns = {"segment:fusion.1": 1_000_000, "segment:all-reduce.2": 500_000,
          "segment:all-to-all.3": 1_500_000,
          "segment:all-to-all.4": 2_000_000,
          "segment:all-to-all.5": 2_000_000, "segment:fusion.6": 9_000_000,
          "finish:all-to-all.9": 7}
    summary = SimpleNamespace(
        per_device=[SimpleNamespace(op_self_ns=ns)] * 4,
        program_names={"segment": "jit_mr_segment(5)"})
    job = SimpleNamespace(op_scopes=lambda role: scopes)
    monkeypatch.setattr(obs, "recent", lambda n: [job] * n)
    return SimpleNamespace(trace=summary, jobs=[None, None],
                           tokens_per_job=1_000_000)


@pytest.mark.parametrize("metric,want", [("push_ms_per_mtok", 2.0),
                                         ("steal_ms_per_mtok", 1.5)])
def test_exchange_readers_sum_their_scopes(monkeypatch, metric, want):
    # two jobs of 1 M tokens on four devices alike: push 4 ms, claim and
    # fetch 3 ms, each over 2 M tokens
    got = cells.metric_reader(metric)(_steal_run(monkeypatch))
    assert got == pytest.approx(want)


def test_steal_reader_reads_nothing_where_stealing_is_off(monkeypatch):
    off = {k: v for k, v in STEAL_OPS.items() if k in ("all-to-all.4",
                                                       "all-to-all.5",
                                                       "fusion.6")}
    run_ = _steal_run(monkeypatch, scopes=dict(off, **{
        "fusion.1": "jit(mr_segment)/while/body/route/gather",
        "all-reduce.2": "jit(mr_segment)/while/body/map/x",
        "all-to-all.3": "jit(mr_segment)/while/body/local_reduce/sort"}))
    assert cells.metric_reader("steal_ms_per_mtok")(run_) is None
    assert cells.metric_reader("push_ms_per_mtok")(run_) == pytest.approx(2.0)


def test_work_imbalance_sums_the_traced_jobs():
    read = cells.metric_reader("work_imbalance")
    jobs = [SimpleNamespace(work_per_rank=[22, 22, 22, 22]),
            SimpleNamespace(work_per_rank=[64, 8, 8, 8])]
    # summed: 86, 30, 30, 30 over a mean of 44
    assert read(SimpleNamespace(jobs=jobs)) == pytest.approx(86 / 44)
    assert read(SimpleNamespace(jobs=jobs[:1])) == pytest.approx(1.0)
    # a single rank has no balance to read
    one = [SimpleNamespace(work_per_rank=[16])] * 2
    assert read(SimpleNamespace(jobs=one)) is None


def test_exchange_readers_on_the_recorded_four_chip_trace(monkeypatch):
    # two jobs of the four-chip cell at 2^18 tokens, vocab 2^12, task
    # 4096, segment 8 (two segment programs a job), stealing on, on a
    # four-chip TPU v5e host, and the op -> scope map of their segment
    # program
    from bench import trace
    summary = trace.reduce_profile(ProfileData.from_serialized_xspace(
        _fixture("tpu4_wordcount_steal_spans.xplane.pb.gz")), [0, 1, 2, 3],
        [2, 2])
    scopes = json.loads(_fixture(
        "tpu4_wordcount_steal_spans.op_scopes.json.gz"))
    assert summary.devices == [0, 1, 2, 3]
    assert summary.program_names["segment"].startswith("jit_mr_segment(")
    assert all(0 < d.program_ns["segment"] for d in summary.per_device)
    # the exchange is the all-to-alls: two under push, one under fetch
    exchange = {op: path.split("/")[-2] for op, path in scopes.items()
                if op.startswith("all_to_all")}
    assert sorted(exchange.values()) == ["fetch", "push", "push"]
    ran = {key.partition(":")[2] for d in summary.per_device
           for key in d.op_self_ns if key.startswith("segment:")}
    assert set(exchange) <= ran
    job = SimpleNamespace(op_scopes=lambda role: scopes)
    monkeypatch.setattr(obs, "recent", lambda n: [job] * n)
    run_ = SimpleNamespace(trace=summary, jobs=[None, None],
                           tokens_per_job=1 << 18)
    got = {m: cells.metric_reader(m)(run_)
           for m in ("push_ms_per_mtok", "steal_ms_per_mtok")}
    segment = summary.program_s("segment") * 1e3 / (2 * (1 << 18) / 1e6)
    assert all(v > 0 for v in got.values()), got
    assert sum(got.values()) < segment
