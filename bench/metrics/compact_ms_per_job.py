"""compact_ms_per_job (ms): device self time of the finish program's ops
under the scope ``compact`` (the one sort, run sums and compaction of a
log window of two-lane keys) per job, averaged over the devices.

The op -> scope map comes from the compiled text of the finish program
that ran (``JobTrace.op_scopes("finish")``). An op of the trace that the
map lacks, or a finish program not named ``jit_mr_finish``, is refused,
as in ``bench/spans.py``. None where the program keeps no finish program
for ``op_scopes`` or has no op under ``compact``."""
from bench.trace import TraceMismatch

SCOPE = "compact"


def _finish_scopes():
    try:
        from repro.core import obs
    except ImportError:
        return None
    traces = obs.recent(1)
    if not traces:
        return None
    try:
        return traces[0].op_scopes("finish")
    except KeyError:                 # the job noted no finish program
        return None


def read(run):
    scopes = _finish_scopes()
    if scopes is None:
        return None
    paths = {op: set(p.split("/")) for op, p in scopes.items()}
    if not any(SCOPE in p for p in paths.values()):
        return None
    name = run.trace.program_names["finish"]
    if not name.startswith("jit_mr_finish"):
        raise TraceMismatch(f"the finish program is {name!r}, not "
                            "jit_mr_finish: its scopes cannot be named")
    ns = 0
    for d in run.trace.per_device:
        for key, t in d.op_self_ns.items():
            role, _, op = key.partition(":")
            if role != "finish":
                continue
            if op not in paths:
                raise TraceMismatch(f"finish op {op!r} of the trace is not "
                                    "in the compiled program that ran")
            if SCOPE in paths[op]:
                ns += t
    return ns / len(run.trace.per_device) * 1e-6 / len(run.jobs)
