"""bench-guard (benchmarks/check_regression.py): schema + tolerance
gates over the benchmark smoke artifacts, against synthetic fixtures."""
import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))                     # repo root: benchmarks/
from benchmarks.check_regression import (CHECKS, check,  # noqa: E402
                                         group_names, main)

FIG8 = {
    "per_task_size": {"1024": {"resident_s": 1.0, "streamed_s": 1.0}},
    "worst_overlap_win_pct": -2.0,
    "streamed_within_10pct": True,
}
FIG9 = {
    "model": {"rows": [{"s": 1.6, "t_2s": 2.0, "t_steal": 1.0}]},
    "real": {"per_skew": {"0.0": {}}},
    "steal_overhead_pct_worst": 6.0,
    "criteria": {"steal_beats_2s_at_max_skew": True, "oracle_exact": True},
}
FIG10 = {
    "model": {"rows": [{"a": 2.2, "per_part": {}}]},
    "real": {"per_skew": {"2.2": {}}},
    "partitioner_overhead_pct_worst": 3.0,
    "criteria": {"sampled_beats_hash_at_max_skew": True,
                 "split_beats_hash_at_max_skew": True,
                 "win_split_vs_hash_reduce_pct": 70.0,
                 "oracle_exact": True},
}
FIG11 = {
    "per_k": {"16": {"policies": {}}},
    "criteria": {"max_K": 16,
                 "fairshare_p95_win_pct": 41.0,
                 "fair_vs_fifo_makespan_pct": -14.0,
                 "jain_fair": 0.48,
                 "fair_jain_beats_fifo": True,
                 "priority_favors_high": True,
                 "all_jobs_exact": True},
}
FIG13 = {
    "P": 8, "P_new": 6, "K": 4, "kill_tick": 12,
    "clean": {"wall_s": 4.0, "ticks": 24, "exact": True, "final_p": 8,
              "recoveries": []},
    "recover": {"wall_s": 4.8, "ticks": 26, "exact": True, "final_p": 6,
                "recoveries": [{"tick": 12, "p_old": 8, "p_new": 6,
                                "seconds": 0.4, "restored": 4,
                                "scratch": 0}]},
    "restart": {"wall_s": 7.5, "ticks": 40, "exact": True, "final_p": 6,
                "recoveries": [{"tick": 12, "p_old": 8, "p_new": 6,
                                "seconds": 0.1, "restored": 0,
                                "scratch": 4}]},
    "criteria": {"records_equal": True,
                 "all_jobs_elastic_restored": True,
                 "mttr_s": 0.4,
                 "recovery_overhead_pct": 20.0,
                 "restart_overhead_pct": 87.5,
                 "recovery_win_vs_restart_pct": 36.0,
                 "recovery_beats_restart": True},
}
FIG14 = {
    "model": {"16": {"fair": {}, "fair+cosched": {}}},
    "real": {"P": 8, "per_k": {"4": {"fleets": {}}}},
    "criteria": {"max_K": 16,
                 "cosched_makespan_win_pct": 56.0,
                 "cosched_beats_fair_makespan": True,
                 "cosched_p95_win_pct": 62.0,
                 "jain_fair": 0.55,
                 "jain_cosched": 0.62,
                 "cosched_beats_fair_jain": True,
                 "all_jobs_exact": True,
                 "crossjob_steals_real": 94,
                 "crossjob_stealing_active": True,
                 "one_domain_per_fleet": True},
}
FIG15 = {
    "skews": [0.0, 1.6], "code_rates": [1, 2, 3],
    "real": {"P": 6, "per_skew": {"1.6": {}}},
    "bytes": {"per_step_blocks": {"1": 5, "2": 3, "3": 2},
              "shuffle_ratio_at_max_skew": {"2": 0.6, "3": 0.4}},
    "criteria": {"shuffle_ratio_r2_at_max_skew": 0.6,
                 "shuffle_ratio_r3_at_max_skew": 0.4,
                 "bytes_win_r2_pct": 40.0,
                 "bytes_win_r3_pct": 60.0,
                 "r2_le_065_at_max_skew": True,
                 "records_equal": True,
                 "oracle_exact": True},
}


@pytest.fixture()
def dirs(tmp_path):
    results = tmp_path / "results"
    baseline = tmp_path / "baseline"
    results.mkdir()
    baseline.mkdir()

    def write(fig8=FIG8, fig9=FIG9, fig10=FIG10, fig11=FIG11,
              fig13=FIG13, fig14=FIG14, fig15=FIG15,
              fresh_fig8=None, fresh_fig9=None, fresh_fig10=None,
              fresh_fig11=None, fresh_fig13=None, fresh_fig14=None,
              fresh_fig15=None):
        (baseline / "BENCH_io_overlap.json").write_text(json.dumps(fig8))
        (baseline / "BENCH_imbalance.json").write_text(json.dumps(fig9))
        (baseline / "BENCH_keyskew.json").write_text(json.dumps(fig10))
        (baseline / "BENCH_multitenant.json").write_text(json.dumps(fig11))
        (baseline / "BENCH_elastic.json").write_text(json.dumps(fig13))
        (baseline / "BENCH_crossjob.json").write_text(json.dumps(fig14))
        (baseline / "BENCH_coded.json").write_text(json.dumps(fig15))
        (results / "fig8_io_overlap.json").write_text(
            json.dumps(fresh_fig8 if fresh_fig8 is not None else fig8))
        (results / "fig9_imbalance.json").write_text(
            json.dumps(fresh_fig9 if fresh_fig9 is not None else fig9))
        (results / "fig10_keyskew.json").write_text(
            json.dumps(fresh_fig10 if fresh_fig10 is not None else fig10))
        (results / "fig11_multitenant.json").write_text(
            json.dumps(fresh_fig11 if fresh_fig11 is not None else fig11))
        (results / "fig13_elastic.json").write_text(
            json.dumps(fresh_fig13 if fresh_fig13 is not None else fig13))
        (results / "fig14_crossjob.json").write_text(
            json.dumps(fresh_fig14 if fresh_fig14 is not None else fig14))
        (results / "fig15_coded.json").write_text(
            json.dumps(fresh_fig15 if fresh_fig15 is not None else fig15))

    return str(results), str(baseline), write


def test_clean_artifacts_pass(dirs):
    results, baseline, write = dirs
    write()
    assert check("fig8", results, baseline) == []
    assert check("fig9", results, baseline) == []
    assert check("fig10", results, baseline) == []
    assert check("fig11", results, baseline) == []
    assert check("fig13", results, baseline) == []
    assert check("fig14", results, baseline) == []
    assert check("fig15", results, baseline) == []
    assert main(["fig8", "fig9", "fig10", "fig11", "fig13", "fig14",
                 "fig15", "--results", results, "--baseline",
                 baseline]) == 0


def test_missing_fresh_artifact_fails(dirs, tmp_path):
    results, baseline, write = dirs
    write()
    empty = tmp_path / "empty"
    empty.mkdir()
    errs = check("fig8", str(empty), baseline)
    assert errs and "missing" in errs[0]


def test_missing_required_key_fails(dirs):
    results, baseline, write = dirs
    bad = copy.deepcopy(FIG9)
    del bad["criteria"]["steal_beats_2s_at_max_skew"]
    write(fresh_fig9=bad)
    errs = check("fig9", results, baseline)
    assert any("steal_beats_2s_at_max_skew" in e for e in errs)
    assert main(["fig9", "--results", results, "--baseline", baseline]) == 1


def test_tolerance_breach_fails_and_within_passes(dirs):
    results, baseline, write = dirs
    # fig8: win may drop at most 25pp below baseline (-2.0)
    ok = dict(FIG8, worst_overlap_win_pct=-20.0)
    bad = dict(FIG8, worst_overlap_win_pct=-40.0)
    write(fresh_fig8=ok)
    assert check("fig8", results, baseline) == []
    write(fresh_fig8=bad)
    errs = check("fig8", results, baseline)
    assert any("regressed" in e for e in errs)
    # fig9: steal overhead may rise at most 30pp above baseline (6.0)
    worse = copy.deepcopy(FIG9)
    worse["steal_overhead_pct_worst"] = 50.0
    write(fresh_fig9=worse)
    errs = check("fig9", results, baseline)
    assert any("steal_overhead_pct_worst" in e for e in errs)


def test_require_true_criteria_enforced(dirs):
    results, baseline, write = dirs
    lost = copy.deepcopy(FIG9)
    lost["criteria"]["steal_beats_2s_at_max_skew"] = False
    write(fresh_fig9=lost)
    errs = check("fig9", results, baseline)
    assert any("expected true" in e for e in errs)


def test_fig10_gates(dirs):
    """The key-skew guard: win may shrink at most 40pp below baseline
    (70), exactness and both beats-hash criteria are hard-required."""
    results, baseline, write = dirs
    ok = copy.deepcopy(FIG10)
    ok["criteria"]["win_split_vs_hash_reduce_pct"] = 45.0   # within 40pp
    write(fresh_fig10=ok)
    assert check("fig10", results, baseline) == []
    bad = copy.deepcopy(FIG10)
    bad["criteria"]["win_split_vs_hash_reduce_pct"] = 20.0  # breach
    write(fresh_fig10=bad)
    assert any("win_split_vs_hash_reduce_pct" in e
               for e in check("fig10", results, baseline))
    inexact = copy.deepcopy(FIG10)
    inexact["criteria"]["oracle_exact"] = False
    write(fresh_fig10=inexact)
    assert any("oracle_exact" in e and "expected true" in e
               for e in check("fig10", results, baseline))


def test_fig11_gates(dirs):
    """The multi-tenant guard: fair-share p95 win may shrink at most
    35pp below baseline (41), fair-fleet makespan may rise at most 25pp
    above it, per-job exactness + jain ordering are hard-required."""
    results, baseline, write = dirs
    ok = copy.deepcopy(FIG11)
    ok["criteria"]["fairshare_p95_win_pct"] = 10.0     # within 35pp of 41
    ok["criteria"]["fair_vs_fifo_makespan_pct"] = 5.0  # within 25pp
    write(fresh_fig11=ok)
    assert check("fig11", results, baseline) == []
    # p95 win collapsing to ~FIFO trips the min gate
    bad = copy.deepcopy(FIG11)
    bad["criteria"]["fairshare_p95_win_pct"] = 2.0
    write(fresh_fig11=bad)
    assert any("fairshare_p95_win_pct" in e
               for e in check("fig11", results, baseline))
    # slicing overhead ballooning the makespan trips the max gate
    slow = copy.deepcopy(FIG11)
    slow["criteria"]["fair_vs_fifo_makespan_pct"] = 30.0
    write(fresh_fig11=slow)
    assert any("fair_vs_fifo_makespan_pct" in e
               for e in check("fig11", results, baseline))
    # a diverging job is a hard failure
    inexact = copy.deepcopy(FIG11)
    inexact["criteria"]["all_jobs_exact"] = False
    write(fresh_fig11=inexact)
    assert any("all_jobs_exact" in e and "expected true" in e
               for e in check("fig11", results, baseline))


def test_group_expansion_matches_registry(dirs):
    """--group resolves through run.py's REGISTRY: every guarded bench
    lands in exactly one group, and the union covers CHECKS — so CI
    consumes one list and a new figure needs no workflow edit."""
    results, baseline, write = dirs
    bench, chaos = group_names("bench"), group_names("chaos")
    assert "fig14" in bench and "fig13" in chaos
    assert not set(bench) & set(chaos)
    assert set(bench) | set(chaos) == set(group_names("all")) == set(CHECKS)
    write()
    assert main(["--group", "all",
                 "--results", results, "--baseline", baseline]) == 0


def test_fig13_gates(dirs):
    """The elastic guard: recovery overhead over clean may rise at most
    75pp above baseline (20); exactness, restore-without-resubmission,
    and recovery-beats-restart are hard-required."""
    results, baseline, write = dirs
    ok = copy.deepcopy(FIG13)
    ok["criteria"]["recovery_overhead_pct"] = 80.0   # within 75pp of 20
    write(fresh_fig13=ok)
    assert check("fig13", results, baseline) == []
    bloated = copy.deepcopy(FIG13)
    bloated["criteria"]["recovery_overhead_pct"] = 120.0  # breach
    write(fresh_fig13=bloated)
    assert any("recovery_overhead_pct" in e
               for e in check("fig13", results, baseline))
    # a kill that forces even one from-scratch restart is a hard failure
    scratched = copy.deepcopy(FIG13)
    scratched["criteria"]["all_jobs_elastic_restored"] = False
    write(fresh_fig13=scratched)
    assert any("all_jobs_elastic_restored" in e and "expected true" in e
               for e in check("fig13", results, baseline))
    # recovery slower than restart-from-scratch defeats the subsystem
    pointless = copy.deepcopy(FIG13)
    pointless["criteria"]["recovery_beats_restart"] = False
    write(fresh_fig13=pointless)
    assert any("recovery_beats_restart" in e
               for e in check("fig13", results, baseline))


def test_fig14_gates(dirs):
    """The cross-job guard: the co-scheduled makespan win may shrink at
    most 30pp below baseline (56); beating fair on makespan AND Jain,
    per-job exactness, and live cross-rank steals are hard-required,
    with an absolute 0.30 Jain floor on the co-scheduled fleet."""
    results, baseline, write = dirs
    ok = copy.deepcopy(FIG14)
    ok["criteria"]["cosched_makespan_win_pct"] = 30.0  # within 30pp of 56
    write(fresh_fig14=ok)
    assert check("fig14", results, baseline) == []
    shrunk = copy.deepcopy(FIG14)
    shrunk["criteria"]["cosched_makespan_win_pct"] = 10.0  # breach
    write(fresh_fig14=shrunk)
    assert any("cosched_makespan_win_pct" in e
               for e in check("fig14", results, baseline))
    # a domain that wins makespan by starving its small members fails
    # the fairness leg outright
    unfair = copy.deepcopy(FIG14)
    unfair["criteria"]["cosched_beats_fair_jain"] = False
    write(fresh_fig14=unfair)
    assert any("cosched_beats_fair_jain" in e and "expected true" in e
               for e in check("fig14", results, baseline))
    # ... and the Jain floor is absolute, baseline notwithstanding
    starved_base = copy.deepcopy(FIG14)
    starved_base["criteria"]["jain_cosched"] = 0.10
    starved = copy.deepcopy(FIG14)
    starved["criteria"]["jain_cosched"] = 0.15
    write(fig14=starved_base, fresh_fig14=starved)
    assert any("jain_cosched" in e and "floor" in e
               for e in check("fig14", results, baseline))
    # a co-scheduled job diverging from its solo records is the one
    # unforgivable regression
    inexact = copy.deepcopy(FIG14)
    inexact["criteria"]["all_jobs_exact"] = False
    write(fresh_fig14=inexact)
    assert any("all_jobs_exact" in e and "expected true" in e
               for e in check("fig14", results, baseline))
    # a "win" with zero cross-rank steals is a bookkeeping artifact
    idle = copy.deepcopy(FIG14)
    idle["criteria"]["crossjob_stealing_active"] = False
    write(fresh_fig14=idle)
    assert any("crossjob_stealing_active" in e
               for e in check("fig14", results, baseline))


def test_fig15_gates(dirs):
    """The coded-shuffle guard: the r=2 bytes win may shrink at most
    10pp below baseline (40); the 0.65x acceptance ratio, record
    identity with r=1, and oracle exactness are hard-required."""
    results, baseline, write = dirs
    ok = copy.deepcopy(FIG15)
    ok["criteria"]["bytes_win_r2_pct"] = 32.0    # within 10pp of 40
    write(fresh_fig15=ok)
    assert check("fig15", results, baseline) == []
    shrunk = copy.deepcopy(FIG15)
    shrunk["criteria"]["bytes_win_r2_pct"] = 25.0   # breach
    write(fresh_fig15=shrunk)
    assert any("bytes_win_r2_pct" in e
               for e in check("fig15", results, baseline))
    # the acceptance headline is hard-required: r=2 must keep shuffle
    # bytes at or under 0.65x the r=1 reference
    over = copy.deepcopy(FIG15)
    over["criteria"]["r2_le_065_at_max_skew"] = False
    write(fresh_fig15=over)
    assert any("r2_le_065_at_max_skew" in e and "expected true" in e
               for e in check("fig15", results, baseline))
    # a coded run diverging from the r=1 records (or the host oracle)
    # is the one unforgivable regression
    inexact = copy.deepcopy(FIG15)
    inexact["criteria"]["records_equal"] = False
    write(fresh_fig15=inexact)
    assert any("records_equal" in e and "expected true" in e
               for e in check("fig15", results, baseline))


def test_fig15_bytes_floor_is_absolute(dirs):
    """The bytes-win floor is baseline-independent: a silently-
    degenerate r=1 fallback (coded path not engaging, 0% win) fails
    even against a baseline that recorded the same degeneracy."""
    results, baseline, write = dirs
    flat_base = copy.deepcopy(FIG15)
    flat_base["criteria"]["bytes_win_r2_pct"] = 0.0
    flat = copy.deepcopy(FIG15)
    flat["criteria"]["bytes_win_r2_pct"] = 0.0
    write(fig15=flat_base, fresh_fig15=flat)
    errs = check("fig15", results, baseline)
    assert any("bytes_win_r2_pct" in e and "floor" in e for e in errs)


def test_fig11_fairness_floor_is_absolute(dirs):
    """The jain floor is baseline-independent: even a baseline that
    (hypothetically) recorded terrible fairness cannot excuse a fresh
    run below 0.30."""
    results, baseline, write = dirs
    low_base = copy.deepcopy(FIG11)
    low_base["criteria"]["jain_fair"] = 0.10
    unfair = copy.deepcopy(FIG11)
    unfair["criteria"]["jain_fair"] = 0.15
    write(fig11=low_base, fresh_fig11=unfair)
    errs = check("fig11", results, baseline)
    assert any("jain_fair" in e and "floor" in e for e in errs)
    # and a missing floor metric is reported, not skipped
    gone = copy.deepcopy(FIG11)
    del gone["criteria"]["jain_fair"]
    write(fresh_fig11=gone)
    errs = check("fig11", results, baseline)
    assert any("jain_fair" in e for e in errs)
