"""The reduction of a profiler trace to device times (``bench/trace.py``).

Two kinds of trace: small synthetic ones written as XSpace text protos,
whose every number is known, and a trace recorded on a TPU v5e of two tiny
WordCount jobs through the harness (``fixtures/``), which pins the names
the TPU gives its planes, lines and programs.
"""
import gzip
import os
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from bench import cells, trace

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _line(lid, name, events, meta):
    out = [f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0']
    for ev_name, start, dur in events:
        key = meta.setdefault(ev_name, len(meta) + 1)
        out.append(f"events {{ metadata_id: {key} offset_ps: {start * 1000}"
                   f" duration_ps: {dur * 1000} }}")
    out.append("}")
    return " ".join(out)


def _plane(pid, name, lines):
    meta, body = {}, []
    for lid, (line_name, events) in enumerate(lines, 1):
        body.append(_line(lid, line_name, events, meta))
    for ev_name, key in meta.items():
        body.append(f'event_metadata {{ key: {key} value {{ id: {key} '
                    f'name: "{ev_name}" }} }}')
    return f'planes {{ id: {pid} name: "{name}" ' + " ".join(body) + " }"


def xspace(devices, spans):
    """A profile of ``devices`` {id: (modules, ops, async_ops)} and host
    ``spans``, each a list of (name, start_ns, duration_ns)."""
    planes = [_plane(1, "/host:CPU", [("python", spans)])]
    for i, (dev, (mods, ops, async_ops)) in enumerate(devices.items(), 2):
        planes.append(_plane(i, f"/device:TPU:{dev}", [
            ("XLA Modules", mods), ("XLA Ops", ops),
            ("Async XLA Ops", async_ops)]))
    return ProfileData.from_text_proto("\n".join(planes))


INIT, SEG, FIN = "jit__lambda(1)", "jit__lambda(2)", "jit__lambda(3)"
SPANS = [("bench.submit", 0, 100), ("bench.step", 100, 700),
         ("bench.result", 800, 200), ("bench.compare", 1000, 500),
         ("bench.submit", 1500, 100), ("bench.step", 1600, 400),
         ("bench.result", 2000, 100)]


def _device():
    mods = [(INIT, 50, 20), (SEG, 200, 200), (SEG, 450, 200),
            (FIN, 850, 100),
            (INIT, 1550, 20), (SEG, 1700, 200), (FIN, 2010, 50)]
    ops = [("%init.1 = s32[] x", 50, 20),
           ("%while.3 = (s32[]) while(x)", 200, 200),   # holds two ops
           ("%fusion.7 = s32[4] fusion(x)", 210, 50),
           ("%fusion.8 = s32[4] fusion(x)", 300, 80),
           ("%fusion.7 = s32[4] fusion(x)", 450, 200),
           ("%combine.2 = s32[4] fusion(x)", 850, 100),
           ("%init.1 = s32[] x", 1550, 20),
           ("%fusion.7 = s32[4] fusion(x)", 1700, 200),
           ("%combine.2 = s32[4] fusion(x)", 2010, 50),
           ("%late.1 = s32[] x", 3000, 10)]              # after the jobs
    return mods, ops, []


def test_roles_busy_time_and_gaps_of_a_synthetic_trace():
    s = trace.reduce_profile(xspace({0: _device()}, SPANS), [0], [2, 1])
    assert s.program_names == {"init": INIT, "segment": SEG, "finish": FIN}
    assert s.program_s("segment") == pytest.approx(600e-9)
    assert s.program_s("init") == pytest.approx(40e-9)
    assert s.program_s("finish") == pytest.approx(150e-9)
    # the window is the two jobs, [0, 1000) and [1500, 2100); the
    # compare between them and the op after them lie outside
    assert s.window_s == pytest.approx(1600e-9)
    assert s.busy_s == pytest.approx(790e-9)
    b = s.breakdown()
    ops = dict(b["device_ops"])
    # self time: the loop's own time leaves out the two ops inside it
    assert ops["segment:while.3"] == pytest.approx(70e-9)
    assert ops["segment:fusion.7"] == pytest.approx(450e-9)
    gaps = b["idle_gaps"]
    assert sum(g for _, g in gaps) == pytest.approx(810e-9)
    assert gaps[0] == ["bench.step", pytest.approx(200e-9)]
    assert {n for n, _ in gaps} <= set(trace.SPANS)
    # idle inside each kind of span: result() idles 100 + 50 ns, and the
    # compare lies outside the window
    idle = {n: s.idle_in_s(n) for n in trace.SPANS}
    assert idle == pytest.approx({"bench.submit": 160e-9,
                                  "bench.step": 500e-9,
                                  "bench.result": 150e-9,
                                  "bench.compare": 0.0})
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)


def test_devices_averaged_and_their_gaps_named_by_device():
    mods, ops, a = _device()
    slow = [(t, start, dur * 2) if t.startswith("%fusion.7") and start == 450
            else (t, start, dur) for t, start, dur in ops]
    s = trace.reduce_profile(
        xspace({0: (mods, slow, a), 1: _device()}, SPANS), [0, 1], [2, 1])
    one = trace.reduce_profile(xspace({1: _device()}, SPANS), [1], [2, 1])
    assert s.devices == [0, 1]
    # fusion.7 at 450 runs 200 ns longer on device 0: 150 of them in the
    # step, 50 in result()
    assert s.busy_s == pytest.approx(one.busy_s + 200e-9 / 2)
    assert s.idle_in_s("bench.step") == pytest.approx(
        one.idle_in_s("bench.step") - 150e-9 / 2)
    assert s.idle_in_s("bench.result") == pytest.approx(
        one.idle_in_s("bench.result") - 50e-9 / 2)
    assert all(n.startswith("tpu") for n, _ in s.breakdown()["idle_gaps"])


def test_a_program_count_that_does_not_match_fails_loudly():
    prof = xspace({0: _device()}, SPANS)
    with pytest.raises(trace.TraceMismatch, match="ran 7 programs"):
        trace.reduce_profile(prof, [0], [2, 2])
    with pytest.raises(trace.TraceMismatch, match="bench.submit"):
        trace.reduce_profile(prof, [0], [5])
    with pytest.raises(trace.TraceMismatch, match="no trace plane"):
        trace.reduce_profile(prof, [0, 1], [2, 1])


def test_programs_out_of_the_expected_order_fail_loudly():
    mods, ops, a = _device()
    mods[1], mods[3] = (SEG, 200, 200), (SEG, 850, 100)   # no finish
    with pytest.raises(trace.TraceMismatch, match="names"):
        trace.reduce_profile(xspace({0: (mods, ops, a)}, SPANS), [0],
                             [2, 1])


def test_intervals_merge_and_clip():
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3],
                                                            [5, 8]]
    assert trace._clip([[0, 3], [5, 8]], [[2, 6]]) == [[2, 3], [5, 6]]
    assert trace._length([[2, 3], [5, 6]]) == 2


def _fixture(name):
    with gzip.open(os.path.join(FIXTURES, name), "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def test_recorded_tpu_trace_of_two_jobs():
    # two WordCount jobs of 2^16 tokens at vocab 2^12, task 4096, segment
    # 8: two segment programs each, on one TPU v5e
    s = trace.reduce_profile(_fixture("tpu1_wordcount.xplane.pb.gz"), [0],
                             [2, 2])
    assert len(set(s.program_names.values())) == 3
    assert all(n.startswith("jit__lambda(") for n in
               s.program_names.values())
    assert 0 < s.busy_s < s.window_s
    assert s.program_s("segment") > s.program_s("init") > 0
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert {n for n, _ in b["idle_gaps"]} <= set(trace.SPANS) | {"none"}
    with pytest.raises(trace.TraceMismatch):
        trace.reduce_profile(_fixture("tpu1_wordcount.xplane.pb.gz"), [0],
                             [2, 3])


def test_metric_readers_on_the_recorded_trace():
    s = trace.reduce_profile(_fixture("tpu1_wordcount.xplane.pb.gz"), [0],
                             [2, 2])
    job = SimpleNamespace(segments=2, prefetch_misses=1, work_per_rank=[16])
    run = SimpleNamespace(trace=s, jobs=[job, job], tokens_per_job=1 << 16,
                          chips=1, device_kind="TPU v5 lite")
    # the metrics every cell reports; those scoped to some cells are read
    # from the four-chip trace below
    names = [m["name"] for m in cells.load_benchmark()["per_layer"]
             if "workloads" not in m]
    values = {m: cells.metric_reader(m)(run) for m in names}
    assert set(values) == {
        "device_idle_share", "segment_device_ms_per_mtok",
        "segment_roofline", "finish_device_ms_per_job",
        "result_host_ms_per_job", "feed_sync_share"}
    assert 0 < values["device_idle_share"] < 100
    assert 0 < values["segment_roofline"] < 100
    assert values["segment_device_ms_per_mtok"] > 0
    assert values["finish_device_ms_per_job"] > 0
    assert values["result_host_ms_per_job"] == pytest.approx(
        1e3 * s.idle_in_s("bench.result") / 2)
    assert 0 < values["result_host_ms_per_job"] < 1e3 * s.window_s / 2
    assert values["feed_sync_share"] == pytest.approx(50.0)
