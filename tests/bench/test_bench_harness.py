"""The harness: lookup by name, BENCHMARK.json's shape, the roofline
arithmetic, the refusal of the CPU, and the reference against the engine
on the CPU at a tiny size."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import cells, run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SPEC = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_benchmark_json_keeps_to_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert not m["name"].endswith("_roofline") or m["unit"] == "%"
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert all(NAME.match(k) for k in c["reduced"])


@pytest.mark.parametrize("name", CELLS)
def test_every_part_of_a_cell_is_found_by_name(name):
    cell = cells.load_cell(name)
    assert cell.chips * 1 == cell.config["job"]["n_procs"]
    oracle = cells.oracle(cell.usecase_name)
    assert callable(oracle.reference) and callable(oracle.control)
    for m in cell.per_layer:
        assert callable(cells.metric_reader(m.name))
    # what the configuration lists as cut is a key of its own file
    entry = next(c for c in SPEC["configs"] if c["name"] == cell.config_name)
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    assert set(entry["reduced"]) <= set(cell.config)


def test_per_layer_metrics_of_a_cell_follow_their_workloads():
    # a metric with a workloads list is read in the cells it names, one
    # without in every cell, in BENCHMARK.json's order
    scoped = [m for m in SPEC["per_layer"] if "workloads" in m]
    assert scoped and all(set(m["workloads"]) <= set(CELLS) for m in scoped)
    for name in CELLS:
        got = [m.name for m in cells.load_cell(name).per_layer]
        assert got == [m["name"] for m in SPEC["per_layer"]
                       if name in m.get("workloads", CELLS)]
        assert {"device_idle_share", "segment_roofline"} <= set(got)
    four = [m.name for m in cells.load_cell("wc-wiki-4chip-bal").per_layer]
    assert {"work_imbalance", "push_ms_per_mtok",
            "steal_ms_per_mtok"} <= set(four)
    for name in ("wc-wiki-1chip", "hist-ratings-1chip"):
        got = [m.name for m in cells.load_cell(name).per_layer]
        assert got == [m["name"] for m in SPEC["per_layer"]
                       if "workloads" not in m]


def test_a_metric_scoped_to_no_cell_is_an_error(monkeypatch):
    spec = json.loads(json.dumps(SPEC))
    spec["per_layer"][0]["workloads"] = ["wc-wiki-1chip", "no-such-cell"]
    monkeypatch.setattr(cells, "load_benchmark", lambda: spec)
    with pytest.raises(ValueError, match="no-such-cell"):
        cells.load_cell("hist-ratings-1chip")


def test_unknown_names_are_errors():
    with pytest.raises(KeyError, match="no workload"):
        cells.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        cells.oracle("no-such-usecase")
    with pytest.raises(FileNotFoundError):
        cells.metric_reader("no_such_metric")


def test_roofline_arithmetic_and_unknown_device_kind():
    mod = cells._load_module(
        cells.BENCH / "metrics" / "segment_roofline.py", "metric")
    least = mod.least_seconds(1 << 24, 1, "TPU v5 lite")
    assert least == pytest.approx(4 * (1 << 24) / 819e9)
    assert mod.least_seconds(1 << 24, 4, "TPU v5 lite") == pytest.approx(
        least / 4)
    with pytest.raises(KeyError, match="no peaks"):
        mod.least_seconds(1 << 24, 1, "TPU v99")
    with pytest.raises(KeyError, match="no peaks"):
        cells.peaks("cpu")


def test_oracles_import_nothing_of_the_program():
    for f in (cells.BENCH / "oracles").glob("*.py"):
        assert "repro" not in f.read_text(), f


def test_records_wrong_counts_each_differing_record():
    want = {1: 5, 2: 7, 3: 1}
    assert run.records_wrong(dict(want), want) == 0
    assert run.records_wrong({1: 5, 2: 8, 3: 1}, want) == 1
    assert run.records_wrong({1: 5, 2: 7}, want) == 1
    assert run.records_wrong({1: 5, 2: 7, 3: 1, 9: 1}, want) == 1
    assert run.records_wrong({}, want) == 3
    # records kept as sorted arrays read the same
    kept = run.as_arrays({3: 1, 1: 5, 2: 8})
    assert kept[0].tolist() == [1, 2, 3] and kept[1].tolist() == [5, 8, 1]
    assert run.records_wrong(kept, want) == 1
    assert run.records_wrong(kept, run.as_arrays(want)) == 1


def test_a_metric_with_nothing_to_read_is_an_error(monkeypatch):
    cell = cells.load_cell(CELLS[0])
    monkeypatch.setattr(cells, "metric_reader", lambda name: lambda ctx: None)
    with pytest.raises(RuntimeError, match="nothing to read"):
        run.read_metrics(cell, None)


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "wc-wiki-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("name", ["wc-wiki-1chip", "hist-ratings-1chip"])
def test_engine_equals_reference_on_the_cpu(name, tiny_cell, cpu_devices):
    cell = tiny_cell(name, task_size=1024, push_cap=256)
    logged = []
    line, checks = run.run_cell(cell, cpu_devices, 2 ** 31 + 3, 0.2, False,
                                log=logged.append)
    # set-up ends before the reference runs, and the reference runs once
    # the window has closed and the device's peak has been read
    order = [m.split(":")[0] for m in logged]
    assert order.index("setup") < order.index("device peak memory") < (
        order.index("reference and comparison"))
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["checks"]["records_wrong"] == {"value": 0, "limit": 0}
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == 1
    assert checks == ["check records_wrong: 0 (limit 0)",
                      "check jobs_failed: 0 (limit 0)"]
    json.dumps(line)


class _Submitted(Exception):
    pass


def test_run_job_calls_submit_as_users_do(monkeypatch):
    # every job goes through submit(config, source) and nothing more
    import repro.core
    calls = []

    def submit(*args, **kw):
        calls.append((args, kw))
        raise _Submitted

    monkeypatch.setattr(repro.core, "submit", submit)
    rec, records = run.run_job("cfg", "source")
    assert rec.failed and records is None
    assert calls == [(("cfg", "source"), {})]


def test_engine_equals_reference_on_four_devices(devices8):
    # the four-chip cell on four CPU devices, stealing on: each task runs
    # once on some rank, and every job's records equal the reference's
    out = devices8(f"""
        import json, sys
        sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "tests", "bench")!r}]
        import jax
        from conftest import make_tiny_cell
        from bench import run
        cell = make_tiny_cell("wc-wiki-4chip-bal", task_size=1024,
                              push_cap=256)
        jobs = []
        real = run.keep
        run.keep = lambda rec, records: jobs.append(rec) or real(rec, records)
        line, checks = run.run_cell(cell, jax.devices()[:4], 2 ** 31 + 9,
                                    0.2, False, log=lambda msg: None)
        print(json.dumps({{"line": line, "checks": checks,
                          "work": [j.work_per_rank for j in jobs],
                          "steals": [j.steals_per_rank for j in jobs]}}))
    """, n_devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    line = got["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert line["checks"]["records_wrong"] == {"value": 0, "limit": 0}
    assert len(got["work"]) == 1 + line["attempted"]
    for work, steals in zip(got["work"], got["steals"]):
        # 16 tasks a rank, each computed once, on whichever rank ran it
        assert len(work) == len(steals) == 4 and sum(work) == 64
