"""Bench guard — fail CI when a smoke artifact is malformed or regressed.

Compares a freshly produced benchmark artifact against the committed
repo-root ``BENCH_*.json`` trajectory file:

  * **schema**: every required key must be present (a benchmark that
    silently stopped emitting its headline number is a regression even
    if it exits 0);
  * **tolerance**: the overhead-style metrics may not be worse than the
    committed baseline by more than a stated margin. Margins are wide —
    CI smoke runs are tiny and the runners are noisy — so only a real
    structural regression (streaming no longer overlapping, the steal
    machinery ballooning) trips them.

    python -m benchmarks.check_regression fig8 fig9
    python -m benchmarks.check_regression fig9 --results results
"""
from __future__ import annotations

import argparse
import json
import os
import sys

try:
    from benchmarks.common import REPO
except ImportError:                      # invoked as a script from benchmarks/
    from common import REPO

# per-benchmark contract: fresh artifact name, committed baseline name,
# required keys (dotted paths), (metric, direction, tolerance) gates, and
# absolute (metric, floor) floors.
# Directions: "min" -> fresh may not drop more than `tol` below baseline;
# "max" -> fresh may not rise more than `tol` above baseline. Floors are
# baseline-independent: the fresh value must be >= the stated minimum
# (for scale-free metrics like a fairness index, where "worse than the
# baseline by N" is the wrong question).
CHECKS: dict[str, dict] = {
    "fig8": {
        "fresh": "fig8_io_overlap.json",
        "baseline": "BENCH_io_overlap.json",
        "required": ["per_task_size", "worst_overlap_win_pct",
                     "streamed_within_10pct"],
        "gates": [
            # streamed may regress vs resident by at most 25 percentage
            # points relative to the committed trajectory
            ("worst_overlap_win_pct", "min", 25.0),
        ],
    },
    "fig9": {
        "fresh": "fig9_imbalance.json",
        "baseline": "BENCH_imbalance.json",
        "required": ["model.rows", "real.per_skew",
                     "steal_overhead_pct_worst",
                     "criteria.steal_beats_2s_at_max_skew",
                     "criteria.oracle_exact"],
        "gates": [
            # the steal machinery's real-run overhead over plain 1s may
            # not balloon past baseline + 30 percentage points
            ("steal_overhead_pct_worst", "max", 30.0),
        ],
        "require_true": ["criteria.steal_beats_2s_at_max_skew",
                         "criteria.oracle_exact"],
    },
    "fig10": {
        "fresh": "fig10_keyskew.json",
        "baseline": "BENCH_keyskew.json",
        "required": ["model.rows", "real.per_skew",
                     "partitioner_overhead_pct_worst",
                     "criteria.sampled_beats_hash_at_max_skew",
                     "criteria.split_beats_hash_at_max_skew",
                     "criteria.win_split_vs_hash_reduce_pct",
                     "criteria.oracle_exact"],
        "gates": [
            # the modeled reduce-path win of the splitting partitioner
            # over static hash may shrink vs the committed trajectory by
            # at most 40 percentage points (smoke runs model a far
            # smaller grid, so the margin is wide on purpose)
            ("criteria.win_split_vs_hash_reduce_pct", "min", 40.0),
            # pre-pass + placement overhead on real runs must not balloon
            # structurally (e.g. the pre-pass re-reading the dataset);
            # smoke engine runs are ~0.1 s on a noisy shared core, so
            # only a blowup past ~100 points over baseline is signal
            ("partitioner_overhead_pct_worst", "max", 100.0),
        ],
        "require_true": ["criteria.sampled_beats_hash_at_max_skew",
                         "criteria.split_beats_hash_at_max_skew",
                         "criteria.oracle_exact"],
    },
    "fig11": {
        "fresh": "fig11_multitenant.json",
        "baseline": "BENCH_multitenant.json",
        "required": ["per_k", "criteria.max_K",
                     "criteria.fairshare_p95_win_pct",
                     "criteria.fair_vs_fifo_makespan_pct",
                     "criteria.jain_fair",
                     "criteria.all_jobs_exact"],
        "gates": [
            # fair share's p95-latency win over FIFO may shrink vs the
            # committed trajectory by at most 35 percentage points (the
            # smoke fleet is much smaller — K=8 vs 16 — so its win is
            # structurally lower; only a collapse to ~FIFO is signal)
            ("criteria.fairshare_p95_win_pct", "min", 35.0),
            # segment-granular slicing must stay ~free: the fair fleet's
            # makespan may not balloon past FIFO's by 25 points more
            # than the committed baseline shows
            ("criteria.fair_vs_fifo_makespan_pct", "max", 25.0),
        ],
        "floors": [
            # absolute fairness floor — Jain index of per-job normalized
            # service under fair share (FIFO sits near 1/K; a fair
            # scheduler that drops under 0.3 is broken regardless of
            # what the baseline says)
            ("criteria.jain_fair", 0.30),
        ],
        "require_true": ["criteria.all_jobs_exact",
                         "criteria.fair_jain_beats_fifo",
                         "criteria.priority_favors_high"],
    },
    "fig13": {
        "fresh": "fig13_elastic.json",
        "baseline": "BENCH_elastic.json",
        "required": ["P", "P_new", "K", "kill_tick",
                     "clean.wall_s", "recover.wall_s", "restart.wall_s",
                     "recover.recoveries",
                     "criteria.mttr_s",
                     "criteria.recovery_overhead_pct",
                     "criteria.restart_overhead_pct",
                     "criteria.recovery_win_vs_restart_pct",
                     "criteria.records_equal",
                     "criteria.all_jobs_elastic_restored",
                     "criteria.recovery_beats_restart"],
        "gates": [
            # surviving a mid-run kill (re-mesh + re-executed
            # since-last-snapshot suffix) may cost at most 75 points
            # more over the clean run than the committed trajectory
            # shows — the smoke fleet is tiny (P=2 -> 1, so the
            # survivors also have half the compute), so only a
            # structural blowup (fold recompiling per job, snapshots
            # re-read per tick) is signal
            ("criteria.recovery_overhead_pct", "max", 75.0),
        ],
        "require_true": [
            # exactness is the whole game: every job in every campaign
            # record-identical to its solo run, kills included
            "criteria.records_equal",
            # the kill was survived WITHOUT resubmission — every job
            # came back via elastic restore, none from scratch
            "criteria.all_jobs_elastic_restored",
            # and restoring beat the restart-from-scratch discipline
            "criteria.recovery_beats_restart",
        ],
    },
    "fig14": {
        "fresh": "fig14_crossjob.json",
        "baseline": "BENCH_crossjob.json",
        "required": ["model", "real.per_k", "criteria.max_K",
                     "criteria.cosched_makespan_win_pct",
                     "criteria.cosched_p95_win_pct",
                     "criteria.jain_fair", "criteria.jain_cosched",
                     "criteria.crossjob_steals_real",
                     "criteria.all_jobs_exact"],
        "gates": [
            # the co-scheduled fleet's modeled makespan win over fig11's
            # fair slicer is structural (K hot tails balanced in one
            # domain vs paid serially); it may shrink vs the committed
            # trajectory by at most 30 percentage points (the smoke
            # model runs at P=8 instead of P=64, where per-rank tails
            # average out more)
            ("criteria.cosched_makespan_win_pct", "min", 30.0),
        ],
        "floors": [
            # absolute fairness floor, as in fig11: the co-scheduled
            # fleet's Jain index over solo/latency must clear 0.30 —
            # a domain that starves its small members behind the giant
            # job's tail is broken regardless of the baseline
            ("criteria.jain_cosched", 0.30),
        ],
        "require_true": [
            # the headline: at the highest K the merged domain beats
            # the fair slicer on BOTH makespan and latency fairness
            "criteria.cosched_beats_fair_makespan",
            "criteria.cosched_beats_fair_jain",
            # exactness: every co-scheduled job reproduced its solo
            # records bit-for-bit, at every K, in both fleets
            "criteria.all_jobs_exact",
            # and the mechanism actually ran — real cross-rank steals
            # inside the merged domain, one domain per fleet
            "criteria.crossjob_stealing_active",
            "criteria.one_domain_per_fleet",
        ],
    },
    "fig15": {
        "fresh": "fig15_coded.json",
        "baseline": "BENCH_coded.json",
        "required": ["skews", "code_rates", "real.per_skew",
                     "bytes.per_step_blocks",
                     "criteria.shuffle_ratio_r2_at_max_skew",
                     "criteria.bytes_win_r2_pct",
                     "criteria.records_equal",
                     "criteria.oracle_exact"],
        "gates": [
            # the coded exchange's shuffle-bytes win over r=1 is
            # structural ((P/r)/(P-1) of the reference at fixed P=6);
            # it may shrink vs the committed trajectory by at most 10
            # percentage points before something is off with the
            # accounting or the exchange itself
            ("criteria.bytes_win_r2_pct", "min", 10.0),
        ],
        "floors": [
            # absolute floor: a silently-degenerate r=1 fallback (the
            # coded path quietly not engaging) scores a 0% win and must
            # fail regardless of what the baseline says
            ("criteria.bytes_win_r2_pct", 20.0),
        ],
        "require_true": [
            # the acceptance headline: r=2 shuffle bytes at most 0.65x
            # the r=1 reference at the largest skew point
            "criteria.r2_le_065_at_max_skew",
            # exactness on real runs, r in {2,3} and the stolen arm:
            # record-identical to r=1 and to the host oracle
            "criteria.records_equal",
            "criteria.oracle_exact",
        ],
    },
}


def dig(obj, path: str):
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return obj


def check(name: str, results_dir: str, baseline_dir: str) -> list[str]:
    spec = CHECKS[name]
    errors: list[str] = []
    fresh_path = os.path.join(results_dir, spec["fresh"])
    base_path = os.path.join(baseline_dir, spec["baseline"])
    if not os.path.isfile(fresh_path):
        return [f"{name}: fresh artifact {fresh_path} missing"]
    with open(fresh_path) as f:
        fresh = json.load(f)
    for key in spec["required"]:
        if dig(fresh, key) is None:
            errors.append(f"{name}: fresh artifact missing key {key!r}")
    for key in spec.get("require_true", []):
        if dig(fresh, key) is not True:
            errors.append(f"{name}: {key} is {dig(fresh, key)!r}, "
                          "expected true")
    for metric, floor in spec.get("floors", []):
        got = dig(fresh, metric)
        if got is None:
            errors.append(f"{name}: floor metric {metric!r} absent")
        elif got < floor:
            errors.append(f"{name}: {metric} below floor: "
                          f"{got:.2f} < {floor}")
    if not os.path.isfile(base_path):
        errors.append(f"{name}: committed baseline {base_path} missing")
        return errors
    with open(base_path) as f:
        base = json.load(f)
    for metric, direction, tol in spec["gates"]:
        got, ref = dig(fresh, metric), dig(base, metric)
        if got is None or ref is None:
            errors.append(f"{name}: gate metric {metric!r} absent "
                          f"(fresh={got!r}, baseline={ref!r})")
            continue
        if direction == "min" and got < ref - tol:
            errors.append(
                f"{name}: {metric} regressed: {got:.2f} < "
                f"baseline {ref:.2f} - tolerance {tol}")
        if direction == "max" and got > ref + tol:
            errors.append(
                f"{name}: {metric} regressed: {got:.2f} > "
                f"baseline {ref:.2f} + tolerance {tol}")
    return errors


def group_names(group: str) -> list[str]:
    """Expand a run.py registry group to the guarded benchmarks in it —
    the same single list ``--smoke-all`` sweeps, so CI's guard step needs
    no hand-maintained figure list either."""
    try:
        from benchmarks.run import REGISTRY
    except ImportError:                  # invoked as a script from benchmarks/
        from run import REGISTRY
    return [b.name for b in REGISTRY if b.name in CHECKS
            and (group == "all" or b.group == group)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("benchmarks", nargs="*", choices=sorted(CHECKS) + [[]],
                    help="which artifacts to guard (or use --group)")
    ap.add_argument("--group", default="",
                    choices=["", "bench", "chaos", "all"],
                    help="guard every registered benchmark in a run.py "
                         "group instead of naming them")
    ap.add_argument("--results", default=os.path.join(REPO, "results"),
                    help="directory holding the fresh artifacts")
    ap.add_argument("--baseline", default=REPO,
                    help="directory holding the committed BENCH_*.json "
                         "baselines (default: the repo root — smoke runs "
                         "never overwrite those)")
    args = ap.parse_args(argv)
    names = list(args.benchmarks) + (group_names(args.group)
                                     if args.group else [])
    if not names:
        ap.error("name benchmarks or pass --group")
    failures: list[str] = []
    for name in names:
        errs = check(name, args.results, args.baseline)
        for e in errs:
            print(f"FAIL {e}")
        if not errs:
            print(f"ok   {name}: schema + tolerances hold")
        failures.extend(errs)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
