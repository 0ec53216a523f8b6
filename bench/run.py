"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name
(``bench/cells.py``). Set-up makes the job's input on the device from the
seed, pulls it to host memory once and serves it to every job through the
program's in-memory ``ArraySource``, and runs one whole warm-up job, which
compiles. Then the window:
jobs run back to back, one client, each a fresh ``submit`` -> ``step()``
until the feed is drained -> ``result()``, timed on the host clock until
``result()`` has returned its records. Jobs started before ``--seconds``
runs out finish and count. After its clock has stopped, each job's records
are kept as sorted arrays; once the window has closed and the peak device
memory has been read, the plain reference runs and every job's records,
the warm-up job's included, are compared with it, record for record.

With ``--trace 0`` the result line carries the end-to-end metrics; with
``--trace 1`` the window is two jobs under the profiler instead, and the
line carries the per-layer metrics, the device's busy and window seconds
and a breakdown. The last line on standard output is one JSON object;
the numbers compared for ``correct`` come last in it (``checks``) and as
the last lines on standard error. The run refuses, with no result line,
a machine where JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# a fixed path inside the checkout: the path is part of the cache's key
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
TRACED_JOBS = 2


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; exits (non-zero, no result) where JAX
    finds no TPU or fewer than ``n`` chips. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (first device: "
                         f"{devs[0].platform} {devs[0].device_kind}); "
                         "this benchmark runs on the chip only")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell asks for {n} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:n]


@dataclass
class JobRecord:
    """One job as the harness saw it."""
    wall_s: float = 0.0          # submit -> result() returned, host clock
    result_s: float = 0.0        # the result() call alone
    segments: int = 0            # FeedStats.segments_built
    prefetch_misses: int = 0
    work_per_rank: list | None = None    # compute-repeats each rank ran
    steals_per_rank: list | None = None  # tasks each rank ran for a peer
    records: tuple | None = None  # (keys, values), sorted by key
    records_wrong: int = 0       # records that differ from the reference
    error: str | None = None     # the exception a failed job raised

    @property
    def failed(self) -> bool:
        return self.error is not None


def as_arrays(records) -> tuple:
    """``records`` ({key: value}, or already (keys, values)) as int64
    arrays sorted by key."""
    if isinstance(records, tuple):
        return records
    keys = np.fromiter(records.keys(), np.int64, len(records))
    values = np.fromiter(records.values(), np.int64, len(records))
    order = np.argsort(keys)
    return keys[order], values[order]


def records_wrong(got, want) -> int:
    """Records of ``got`` and ``want`` that differ: missing, extra or with
    another value."""
    (gk, gv), (wk, wv) = as_arrays(got), as_arrays(want)
    common, gi, wi = np.intersect1d(gk, wk, assume_unique=True,
                                    return_indices=True)
    return (int((gv[gi] != wv[wi]).sum()) + len(gk) - len(common)
            + len(wk) - len(common))


def run_job(job_cfg, source) -> tuple:
    """One job through the users' path, timed; returns its JobRecord and
    the records ``result()`` returned (None where the job raised)."""
    import jax
    from repro.core import submit
    ann = jax.profiler.TraceAnnotation
    rec = JobRecord()
    handle = res = None
    t0 = time.perf_counter()
    try:
        with ann("bench.submit"):
            handle = submit(job_cfg, source)
        with ann("bench.step"):
            while handle.step():
                pass
        t_r = time.perf_counter()
        with ann("bench.result"):
            res = handle.result()
        t1 = time.perf_counter()
    except Exception as e:       # a job that raises is a failed job
        traceback.print_exc(file=sys.stderr)
        rec.error = f"{type(e).__name__}: {e}"[:500]
    finally:
        if handle is not None:
            handle.close()
    if res is None:
        return rec, None
    rec.wall_s, rec.result_s = t1 - t0, t1 - t_r
    st = handle.feed.stats
    rec.segments, rec.prefetch_misses = st.segments_built, st.prefetch_misses
    rec.work_per_rank = np.asarray(res.work_per_rank).tolist()
    rec.steals_per_rank = np.asarray(res.steals_per_rank).tolist()
    return rec, res.records


def keep(rec: JobRecord, records) -> JobRecord:
    """Keep a finished job's records, outside its clock, for the comparison
    after the window."""
    import jax
    if records is not None:
        with jax.profiler.TraceAnnotation("bench.compare"):
            rec.records = as_arrays(records)
    return rec


def compare(cell, tokens: np.ndarray, jobs: list) -> float:
    """Run the plain reference on ``tokens`` and set each finished job's
    ``records_wrong``; returns the seconds that took."""
    from bench import cells
    t = time.perf_counter()
    want = as_arrays(cells.oracle(cell.usecase_name).reference(
        tokens, **cell.config["usecase"]["args"]))
    for j in jobs:
        if j.records is not None:
            j.records_wrong = records_wrong(j.records, want)
    return time.perf_counter() - t


@dataclass
class Setup:
    job_cfg: object
    source: object
    tokens: np.ndarray           # the input every job reads
    warmup: JobRecord
    warmup_records: dict | None  # what the warm-up job's result() returned
    seconds: dict                # set-up phases, host clock


def set_up(cell, seed: int) -> Setup:
    """Everything before the window: the input and one warm-up job."""
    import repro.core
    from repro.data.source import ArraySource

    from bench import generate
    phases = {}
    t = time.perf_counter()
    tokens = generate.make_tokens(cell.traffic["keys"],
                                  int(cell.config["token_ids"]),
                                  cell.tokens_per_job, seed)
    phases["generate_s"] = time.perf_counter() - t
    uc = cell.config["usecase"]
    job_cfg = repro.core.JobConfig(
        usecase=getattr(repro.core, uc["class"])(**uc["args"]),
        **cell.config["job"])
    source = ArraySource(tokens)
    t = time.perf_counter()
    warm, records = run_job(job_cfg, source)
    phases["warmup_job_s"] = time.perf_counter() - t
    return Setup(job_cfg, source, tokens, warm, records, phases)


@dataclass
class RunContext:
    """What a per-layer metric reader reads (``bench/metrics/*.py``)."""
    jobs: list                   # JobRecord of each traced job
    trace: object                # bench.trace.TraceSummary
    tokens_per_job: int
    chips: int
    device_kind: str


def _peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _checks(jobs: list) -> dict:
    """The numbers compared for ``correct``, each with its limit."""
    return {
        "records_wrong": {"value": sum(j.records_wrong for j in jobs),
                          "limit": 0},
        "jobs_failed": {"value": sum(j.failed for j in jobs), "limit": 0},
    }


def trace_options():
    """Profiler options of a traced run: the device trace and the host's
    annotations, without the Python tracer's event per function call."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def read_metrics(cell, ctx: RunContext) -> dict:
    """Each per-layer metric of ``cell``, read from ``ctx``. A reader that
    finds nothing to read in a cell that lists its metric is an error."""
    from bench import cells
    metrics = {}
    for m in cell.per_layer:
        value = cells.metric_reader(m.name)(ctx)
        if value is None:
            raise RuntimeError(f"metric {m.name} found nothing to read in "
                               f"cell {cell.name}, which lists it")
        metrics[m.name] = {"value": value, "unit": m.unit}
    return metrics


def _traced_window(cell, devices, s: Setup, log) -> tuple:
    import jax

    from bench import trace
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR),
                             profiler_options=trace_options())
    try:
        jobs = [keep(*run_job(s.job_cfg, s.source))
                for _ in range(TRACED_JOBS)]
    finally:
        jax.profiler.stop_trace()
    try:
        if any(j.failed for j in jobs):
            return jobs, {}, None, None
        t = time.perf_counter()
        summary = trace.reduce(trace.find_xplane(TRACE_DIR),
                               [d.id for d in devices],
                               [j.segments for j in jobs])
        log(f"trace: read in {time.perf_counter() - t:.1f} s")
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ctx = RunContext(jobs, summary, cell.tokens_per_job, len(devices),
                     devices[0].device_kind)
    return jobs, read_metrics(cell, ctx), summary, summary.breakdown()


def run_cell(cell, devices, seed: int, seconds: float, traced: bool,
             t0: float = _T0, log=None) -> tuple[dict, list]:
    """Run ``cell`` on ``devices``; returns the result line's object and
    the check lines for the end of standard error."""
    from repro.compile_cache import CompileStats
    if log is None:
        def log(msg):
            print(msg, file=sys.stderr, flush=True)
    stats = CompileStats()
    started = time.perf_counter() - t0       # process start, JAX, chips
    s = set_up(cell, seed)
    setup_s = time.perf_counter() - t0
    log(f"setup: {setup_s:.3f} s " + json.dumps(
        {"start_s": round(started, 3),
         **{k: round(v, 3) for k, v in s.seconds.items()}})
        + f", warm-up job {s.warmup.wall_s:.3f} s")
    keep(s.warmup, s.warmup_records)
    s.warmup_records = None
    before = stats.snapshot()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    line = {}
    if traced:
        jobs, metrics, summary, breakdown = _traced_window(cell, devices, s,
                                                           log)
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            line["breakdown"] = breakdown
    else:
        jobs = []
        w0 = time.perf_counter()
        while time.perf_counter() - w0 < seconds:
            jobs.append(keep(*run_job(s.job_cfg, s.source)))
        done = [j for j in jobs if not j.failed]
        metrics = {}
        for m in cell.end_to_end:
            if m.name == "tokens_per_s" and done:
                value = (len(done) * cell.tokens_per_job
                         / sum(j.wall_s for j in done))
            elif m.name == "setup_s":
                value = setup_s
            else:
                continue
            metrics[m.name] = {"value": value, "unit": m.unit}
    device["memory_peak_bytes"] = _peak_memory(devices)
    after = stats.snapshot()
    log(f"compiles in the window: {after['compiles'] - before['compiles']}"
        f" (cache hits {after['cache_hits'] - before['cache_hits']},"
        f" misses {after['cache_misses'] - before['cache_misses']})")
    log(f"device peak memory: {device['memory_peak_bytes']} bytes"
        " (peak_bytes_in_use, fullest chip)")
    log(f"reference and comparison: "
        f"{compare(cell, s.tokens, [s.warmup] + jobs):.3f} s")
    for i, j in enumerate(jobs):
        ranks = (f", work per rank {j.work_per_rank}, steals per rank "
                 f"{j.steals_per_rank}" if len(j.work_per_rank or ()) > 1
                 else "")
        log(f"job {i}: wall {j.wall_s:.4f} s, result {j.result_s:.4f} s, "
            f"segments {j.segments}, prefetch misses {j.prefetch_misses}, "
            f"records wrong {j.records_wrong}{ranks}"
            + (f", FAILED {j.error}" if j.failed else ""))
    checks = _checks([s.warmup] + jobs)
    line.update({
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(jobs),
        "failed": sum(j.failed for j in jobs),
        "metrics": metrics,
        "device": device,
    })
    # the breakdown goes before the checks, which come last
    if "breakdown" in line:
        line["breakdown"] = line.pop("breakdown")
    line["checks"] = checks
    check_lines = [f"check {k}: {c['value']} (limit {c['limit']})"
                   for k, c in checks.items()]
    return line, check_lines


def open_cell(name: str):
    """The cell ``name`` and its chips, with JAX's persistent compilation
    cache kept in the checkout (``CACHE_DIR``)."""
    from bench import cells
    cell = cells.load_cell(name)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    devices = require_chips(cell.chips)
    from repro import compile_cache
    compile_cache.enable()
    return cell, devices


def import_path():
    """Put the checkout's root and ``src`` first on the import path:
    ``bench`` is a package, and its modules must never shadow the
    standard library's (``bench/trace.py``)."""
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import_path()
    cell, devices = open_cell(args.workload)
    line, check_lines = run_cell(cell, devices, args.seed, args.seconds,
                                 bool(args.trace))
    for c in check_lines:
        print(c, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
