"""Per-layer readings of the program's own spans and phase scopes.

The program times its job path in spans (``repro.core.obs``): each
``JobHandle`` keeps a ``JobTrace`` of name -> [count, ns], and the traces
of the last jobs that ended are read back with ``obs.recent(n)``. The
traced window's jobs are the last ``len(run.jobs)`` to end, since the
metrics are read right after it. Each trace must hold one
``mr.segment.dispatch`` per segment its job built, or the reading is
refused.

On the device, each phase of the segment program runs inside a
``jax.named_scope`` (``map``, ``local_reduce``, ``route``, ``push``,
``fold``; ``claim`` and ``fetch`` where stealing is on). The device trace
names ops, not scopes, so the op -> scope map comes from the compiled
text of the program that ran (``JobTrace.op_scopes``). A phase's time is
the self time of the segment program's ops under that scope (or under
any of several), averaged over the devices; a program with no op under
it has nothing to read. An op of the trace that the map lacks, or a
segment program not named ``jit_mr_segment``, is refused: the reading
never guesses.

Where the program has no ``repro.core.obs`` (before it had spans), every
reading here is None.
"""
from __future__ import annotations

from bench.trace import TraceMismatch


def _obs():
    try:
        from repro.core import obs
    except ImportError:
        return None
    return obs


def host_ms_per_job(run, span: str) -> float | None:
    """Milliseconds per traced job inside the program's span ``span``."""
    obs = _obs()
    if obs is None:
        return None
    traces = obs.recent(len(run.jobs))
    got = [t.count("mr.segment.dispatch") for t in traces]
    want = [j.segments for j in run.jobs]
    if got != want:
        raise TraceMismatch(f"the last {len(want)} job traces dispatched "
                            f"{got} segments; the traced jobs built {want}")
    return sum(t.ns(span) for t in traces) * 1e-6 / len(run.jobs)


def segment_ms_per_mtok(run, *scopes: str) -> float | None:
    """Device milliseconds per million input tokens of the segment
    program's ops under any of the phase scopes ``scopes``; None where no
    op of the program that ran carries one of them."""
    obs = _obs()
    if obs is None:
        return None
    name = run.trace.program_names["segment"]
    if not name.startswith("jit_mr_segment"):
        raise TraceMismatch(f"the segment program is {name!r}, not "
                            "jit_mr_segment: its phases cannot be named")
    wanted = set(scopes)
    paths = {op: set(p.split("/")) for op, p in
             obs.recent(1)[0].op_scopes("segment").items()}
    if not any(p & wanted for p in paths.values()):
        return None
    ns = 0
    for d in run.trace.per_device:
        for key, t in d.op_self_ns.items():
            role, _, op = key.partition(":")
            if role != "segment":
                continue
            if op not in paths:
                raise TraceMismatch(f"segment op {op!r} of the trace is not "
                                    "in the compiled program that ran")
            if paths[op] & wanted:
                ns += t
    tokens = run.tokens_per_job * len(run.jobs)
    return ns / len(run.trace.per_device) * 1e-6 / (tokens / 1e6)
