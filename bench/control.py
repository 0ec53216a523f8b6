"""Readings that set the limits of ``correct``, over many seeds in one process.

    python3 bench/control.py --workload <cell> --control-seeds 1,2,3 \\
        --program-seeds 4,5,6,...

``correct`` holds every job's records to the plain reference's, record
for record (``records_wrong``, limit 0). Two readings bound that limit:

* the program's: one job through the timed path per program seed, at the
  cell's own size, exactly as a run's set-up makes and checks it;
* the control's: the reference put in the program's place and computed
  one precision below the configuration's exact int32 counts, with int16
  accumulators (each oracle's ``control``), on the same inputs.

The control must read above the limit, or the comparison could not tell
a narrowed count from an exact one. The benchmark's own runs never run
this; it prints one JSON line with both readings per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_reading(cell, seed: int) -> int:
    """records_wrong of the control's records on the cell's input for
    ``seed``."""
    from bench import cells, generate, run
    args = cell.config["usecase"]["args"]
    tokens = generate.make_tokens(cell.traffic["keys"],
                                  int(cell.config["token_ids"]),
                                  cell.tokens_per_job, seed)
    oracle = cells.oracle(cell.usecase_name)
    return run.records_wrong(oracle.control(tokens, **args),
                             oracle.reference(tokens, **args))


def program_reading(cell, seed: int) -> int:
    """records_wrong of one job through the timed path on the cell's input
    for ``seed``; -1 where the job raised."""
    from bench import run
    s = run.set_up(cell, seed)
    job = run.keep(s.warmup, s.warmup_records)
    run.compare(cell, s.tokens, [job])
    return -1 if job.failed else job.records_wrong


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--program-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)          # bench/ is a package of the root
    from bench import run
    run.import_path()
    cell, _ = run.open_cell(args.workload)
    out = {"workload": cell.name, "limit": 0,
           "program": {}, "control": {}}
    for seed in args.program_seeds:
        out["program"][str(seed)] = program_reading(cell, seed)
        print(f"program seed {seed}: records_wrong "
              f"{out['program'][str(seed)]}", file=sys.stderr, flush=True)
    for seed in args.control_seeds:
        out["control"][str(seed)] = control_reading(cell, seed)
        print(f"control seed {seed}: records_wrong "
              f"{out['control'][str(seed)]}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
