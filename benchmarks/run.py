"""Benchmark harness entry point — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig4,...]
    PYTHONPATH=src python -m benchmarks.run --smoke-all [--group bench]
    PYTHONPATH=src python -m benchmarks.run --quick-all

``REGISTRY`` below is the single list CI consumes: ``--smoke-all`` runs
every registered smoke-capable benchmark in a group and verifies each
one actually wrote a non-empty ``results/*.json`` artifact, so adding a
figure here (plus its ``check_regression`` entry) wires it into the
workflows with NO workflow edits. Groups keep the chaos benchmark
(fig13, its own CI job) out of the default bench sweep.

Outputs land in results/*.json; the console shows the paper-comparison
summaries EXPERIMENTS.md quotes.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import os
import sys
import time
import traceback


@dataclasses.dataclass(frozen=True)
class Bench:
    """One registered benchmark: resolved lazily so importing this
    module (e.g. from check_regression) stays free of jax state."""
    name: str
    module: str                 # import path holding run(quick[, smoke])
    artifact: str               # filename it writes under results/
    smoke: bool = False         # run(smoke=True) supported (CI-sized)
    group: str = "bench"        # CI job family: "bench" | "chaos"


REGISTRY: tuple[Bench, ...] = (
    Bench("fig4", "benchmarks.fig4_scaling", "fig4_scaling.json"),
    Bench("fig5", "benchmarks.fig5_ckpt", "fig5_ckpt.json"),
    Bench("fig6", "benchmarks.fig6_memory", "fig6_memory.json"),
    Bench("fig7", "benchmarks.fig7_timeline", "fig7_timeline.json"),
    Bench("fig8", "benchmarks.fig8_io_overlap", "fig8_io_overlap.json",
          smoke=True),
    Bench("fig9", "benchmarks.fig9_imbalance", "fig9_imbalance.json",
          smoke=True),
    Bench("fig10", "benchmarks.fig10_keyskew", "fig10_keyskew.json",
          smoke=True),
    Bench("fig11", "benchmarks.fig11_multitenant",
          "fig11_multitenant.json", smoke=True),
    Bench("fig13", "benchmarks.fig13_elastic", "fig13_elastic.json",
          smoke=True, group="chaos"),
    Bench("fig14", "benchmarks.fig14_crossjob", "fig14_crossjob.json",
          smoke=True),
    Bench("fig15", "benchmarks.fig15_coded", "fig15_coded.json",
          smoke=True),
    Bench("moe", "benchmarks.moe_dispatch_bench", "moe_dispatch.json"),
    Bench("roofline", "benchmarks.roofline", "roofline.json"),
)


def _run_one(bench: Bench, quick: bool, smoke: bool) -> None:
    fn = importlib.import_module(bench.module).run
    if bench.smoke:
        fn(quick=quick, smoke=smoke)
    else:
        fn(quick=quick)


def _artifact_ok(bench: Bench) -> bool:
    from benchmarks.common import RESULTS
    path = os.path.join(RESULTS, bench.artifact)
    return os.path.isfile(path) and os.path.getsize(path) > 0


def _sweep(benches, quick: bool, smoke: bool) -> list[str]:
    """Run each benchmark and verify its artifact landed non-empty."""
    failed: list[str] = []
    for b in benches:
        print(f"\n===== {b.name} =====")
        t0 = time.time()
        try:
            _run_one(b, quick=quick, smoke=smoke)
            print(f"[{b.name}] done in {time.time()-t0:.1f}s")
        except Exception:
            failed.append(b.name)
            traceback.print_exc()
            continue
        if not _artifact_ok(b):
            failed.append(b.name)
            print(f"[{b.name}] FAIL: results/{b.artifact} missing or "
                  "empty")
    return failed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller datasets / fewer repetitions")
    ap.add_argument("--only", default="",
                    help="comma list of registered names: "
                         + ",".join(b.name for b in REGISTRY))
    ap.add_argument("--smoke-all", action="store_true",
                    help="CI: every smoke-capable benchmark in --group "
                         "at smoke scale, artifact-checked")
    ap.add_argument("--quick-all", action="store_true",
                    help="nightly: every smoke-capable benchmark (all "
                         "groups) at --quick scale, artifact-checked")
    ap.add_argument("--group", default="bench",
                    choices=["bench", "chaos", "all"],
                    help="which CI job family --smoke-all sweeps")
    args = ap.parse_args(argv)

    if args.smoke_all or args.quick_all:
        if args.quick_all:
            benches = [b for b in REGISTRY if b.smoke]
        else:
            benches = [b for b in REGISTRY if b.smoke and
                       (args.group == "all" or b.group == args.group)]
        failed = _sweep(benches, quick=args.quick_all,
                        smoke=args.smoke_all)
        if failed:
            print(f"\nFAILED: {failed}")
            sys.exit(1)
        print(f"\nall {len(benches)} benchmarks complete — results/*.json")
        return

    only = set(filter(None, args.only.split(",")))
    unknown = only - {b.name for b in REGISTRY}
    if unknown:
        ap.error(f"unknown benchmark names: {sorted(unknown)}")
    benches = [b for b in REGISTRY if not only or b.name in only]
    failed = _sweep(benches, quick=args.quick, smoke=False)
    if failed:
        print(f"\nFAILED: {failed}")
        sys.exit(1)
    print("\nall benchmarks complete — results/*.json")


if __name__ == "__main__":
    main()
