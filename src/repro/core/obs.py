"""Spans of the job path, on the profiler's clock and in each job's totals.

``span(name, trace)`` times one piece of work twice over: it opens a
``jax.profiler.TraceAnnotation``, so under a profiler session the span
lands on the host plane beside the device trace, and it adds the elapsed
``perf_counter_ns`` and a count of 1 to ``name`` in ``trace``, a
:class:`JobTrace`. With no profiler session the annotation is inert, and
two clock reads are all a span costs.

Every :class:`~repro.core.job.JobHandle` owns one ``JobTrace``; when its
``result()`` has run, the trace joins a bounded process-wide record that
:func:`recent` reads back, so a process that runs many jobs keeps its
spans in memory and reads them after the work.

The device side names what it runs instead: the three engine programs
are ``jit_mr_init``, ``jit_mr_segment`` and ``jit_mr_finish``
(``core/windows.wrap_segment_fns``), and each phase of a segment sits in
a ``jax.named_scope``. A device trace names ops, not scopes, so
:meth:`JobTrace.op_scopes` maps each op of the program that ran to its
scope, from the compiled program's own text.
"""
from __future__ import annotations

import collections
import time
from contextlib import contextmanager

import jax

from repro.launch.hlo_stats import op_scopes

_RECENT: collections.deque = collections.deque(maxlen=64)


class JobTrace:
    """One job's spans (name -> [count, ns]) and the abstract arguments of
    the programs it ran, for :meth:`op_scopes`."""

    def __init__(self):
        self.spans: dict[str, list[int]] = {}
        self._programs: dict[str, tuple] = {}   # role -> (jitted fn, args)
        self._scopes: dict[str, dict[str, str]] = {}

    def add(self, name: str, ns: int):
        entry = self.spans.setdefault(name, [0, 0])
        entry[0] += 1
        entry[1] += ns

    def count(self, name: str) -> int:
        return self.spans.get(name, (0, 0))[0]

    def ns(self, name: str) -> int:
        return self.spans.get(name, (0, 0))[1]

    def seconds(self, *names: str) -> float:
        """Summed time of the spans ``names``."""
        return sum(self.ns(n) for n in names) * 1e-9

    def note_program(self, role: str, fn, args):
        """Keep the abstract signature of ``fn``'s first call under
        ``role``: each argument's shape, dtype and sharding, no data."""
        if role not in self._programs:
            self._programs[role] = (fn, jax.tree.map(_abstract, args))

    def op_scopes(self, role: str) -> dict[str, str]:
        """``{op name: scope path}`` of the program that ran as ``role``.

        Lowers and compiles the job's own jitted function at the noted
        signature (the compilation cache hands back the executable that
        ran) and reads its ops' metadata; computed on first use only."""
        if role not in self._scopes:
            fn, args = self._programs[role]
            self._scopes[role] = op_scopes(
                fn.lower(*args).compile().as_text())
        return self._scopes[role]


def _abstract(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=getattr(x, "sharding", None))


@contextmanager
def span(name: str, trace: JobTrace | None = None):
    """Time the body as ``name``: a profiler annotation, and, where
    ``trace`` is given, one count and the elapsed nanoseconds in it."""
    t0 = time.perf_counter_ns()
    with jax.profiler.TraceAnnotation(name):
        yield
    if trace is not None:
        trace.add(name, time.perf_counter_ns() - t0)


def finished(trace: JobTrace):
    """Record the trace of a job that has ended."""
    _RECENT.append(trace)


def recent(n: int) -> list[JobTrace]:
    """The traces of the last ``n`` jobs that ended, oldest first."""
    return list(_RECENT)[-n:] if n > 0 else []
