"""Reduce a profiler trace (``.xplane.pb``) of whole jobs to device times.

The trace is read with ``jax.profiler.ProfileData``. On a TPU each chip is
a plane ``/device:TPU:<id>`` with a line ``XLA Modules`` (one event per
program execution, named ``jit_<fn>(<fingerprint>)``) and a line ``XLA
Ops`` (one event per operation, named by its HLO text; loop bodies appear
once per iteration, inside the event of their loop). The benchmark's own
host spans (``bench.submit``, ``bench.step``, ``bench.result``,
``bench.compare``) are events on the host plane ``/host:CPU``.

Which program is which. ``wrap_segment_fns`` jits three lambdas (init,
segment, finish), so their names differ only by fingerprint and say
nothing of their role. The rule here is the order in which they run:
within each traced job every device runs one init, then exactly
``FeedStats.segments_built`` segment programs, then one finish, and
nothing else. So the module events of a device, in time order, must
number ``sum(segments + 2)`` over the traced jobs, and are assigned
init, segment x N, finish, job after job. The assignment is then checked:
all events of one role carry one name, and the three roles three
different names. Any mismatch raises :class:`TraceMismatch`; the
reduction never guesses.

Busy time is the union of the ``XLA Ops`` intervals. The window is the
union of the traced jobs' intervals on the host clock, each from the start
of its ``bench.submit`` span to the end of its ``bench.result`` span (what
the harness does with the records after that lies outside it). Idle gaps
are the parts of the window in which a device runs no operation, each
named by the innermost benchmark span open at its midpoint; the idle time
inside each kind of span is summed over all its spans.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

ROLES = ("init", "segment", "finish")
SPANS = ("bench.submit", "bench.step", "bench.result", "bench.compare")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OP_NAME = re.compile(r"^%?([^\s=]+)")


class TraceMismatch(RuntimeError):
    """The trace does not hold what the traced jobs must have run."""


def find_xplane(log_dir) -> str:
    """The one ``.xplane.pb`` a profiler session wrote under ``log_dir``."""
    found = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise TraceMismatch(f"expected one .xplane.pb under {log_dir}, "
                            f"found {found}")
    return found[0]


def _union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(merged, windows) -> list:
    """``merged`` intervals cut to the union of ``windows`` (both merged)."""
    out, i = [], 0
    for ws, we in windows:
        while i < len(merged) and merged[i][1] <= ws:
            i += 1
        j = i
        while j < len(merged) and merged[j][0] < we:
            out.append([max(merged[j][0], ws), min(merged[j][1], we)])
            j += 1
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


@dataclass
class DeviceTimes:
    """One device's reduced trace, in nanoseconds."""
    busy_ns: int = 0
    program_ns: dict = field(default_factory=lambda: dict.fromkeys(ROLES, 0))
    op_self_ns: dict = field(default_factory=lambda: defaultdict(int))
    gaps: list = field(default_factory=list)   # (span name, ns)
    idle_in_ns: dict = field(default_factory=dict)  # span name -> idle ns


@dataclass
class TraceSummary:
    """Device times of the traced jobs, each averaged over the devices."""
    devices: list                 # device ids, in plane order
    per_device: list              # DeviceTimes
    window_s: float               # union of the jobs' host intervals
    n_jobs: int
    program_names: dict           # role -> module name

    def _mean(self, get) -> float:
        return sum(get(d) for d in self.per_device) / len(self.per_device)

    @property
    def busy_s(self) -> float:
        return self._mean(lambda d: d.busy_ns) * 1e-9

    def program_s(self, role: str) -> float:
        return self._mean(lambda d: d.program_ns[role]) * 1e-9

    def idle_in_s(self, span: str) -> float:
        """Seconds inside the host spans ``span`` in which the device runs
        no operation."""
        return self._mean(lambda d: d.idle_in_ns[span]) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most self time (mean over devices),
        and the longest idle gaps, named by the host span open then."""
        ops = defaultdict(float)
        for d in self.per_device:
            for k, ns in d.op_self_ns.items():
                ops[k] += ns * 1e-9 / len(self.per_device)
        multi = len(self.per_device) > 1
        gaps = [(f"tpu{dev}:{name}" if multi else name, ns * 1e-9)
                for dev, d in zip(self.devices, self.per_device)
                for name, ns in d.gaps]
        return {
            "device_ops": [[k, v] for k, v in
                           sorted(ops.items(), key=lambda x: -x[1])[:top]],
            "idle_gaps": [[k, v] for k, v in
                          sorted(gaps, key=lambda x: -x[1])[:top]],
        }


def _host_spans(profile) -> list:
    spans = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    spans.append((e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns)))
    return sorted(spans, key=lambda s: s[1])


def _job_windows(spans, n_jobs: int) -> list:
    starts = [s for n, s, _ in spans if n == "bench.submit"]
    ends = [e for n, _, e in spans if n == "bench.result"]
    if len(starts) != n_jobs or len(ends) != n_jobs:
        raise TraceMismatch(
            f"{n_jobs} traced jobs, but the host plane holds "
            f"{len(starts)} bench.submit and {len(ends)} bench.result spans")
    windows = list(zip(starts, ends))
    if any(e <= s for s, e in windows) or any(
            windows[k + 1][0] < windows[k][1] for k in range(n_jobs - 1)):
        raise TraceMismatch(f"job spans out of order: {windows}")
    return [list(w) for w in windows]


def _span_at(spans, t: float) -> str:
    """The innermost benchmark span open at host time ``t``."""
    best = None
    for name, s, e in spans:
        if s > t:
            break
        if e >= t:
            best = name
    return best or "none"


def _roles(modules, segments) -> list:
    expect = sum(n + 2 for n in segments)
    if len(modules) != expect:
        raise TraceMismatch(
            f"a device ran {len(modules)} programs in the traced jobs; "
            f"one init, {segments} segments and one finish per job make "
            f"{expect}")
    roles = []
    for n in segments:
        roles += ["init"] + ["segment"] * n + ["finish"]
    return roles


def _device(plane, segments, windows, spans, names) -> DeviceTimes:
    lines = {line.name: line for line in plane.lines}
    for need in ("XLA Modules", "XLA Ops"):
        if need not in lines:
            raise TraceMismatch(f"{plane.name} has no {need!r} line "
                                f"(has {sorted(lines)})")
    modules = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                      e.name) for e in lines["XLA Modules"].events)
    roles = _roles(modules, segments)
    dt = DeviceTimes()
    for (s, e, name), role in zip(modules, roles):
        names[role].add(name)
        dt.program_ns[role] += e - s

    ops = sorted((int(e.start_ns), -int(e.duration_ns), e.name)
                 for e in lines["XLA Ops"].events)
    short = {}
    stack = []                     # (end, key) of the enclosing ops
    m = 0
    intervals = []
    for s, neg, text in ops:
        e = s - neg
        intervals.append((s, e))
        while m < len(modules) and modules[m][1] < s:
            m += 1
        role = roles[m] if m < len(modules) and modules[m][0] <= s else "none"
        op = short.get(text)
        if op is None:
            mo = _OP_NAME.match(text)
            op = short[text] = mo.group(1) if mo else text[:64]
        key = f"{role}:{op}"
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:                  # nested: not the enclosing op's own time
            dt.op_self_ns[stack[-1][1]] -= e - s
        dt.op_self_ns[key] += e - s
        stack.append((e, key))

    busy = _clip(_union(intervals), windows)
    dt.busy_ns = _length(busy)
    # idle: the window less the busy intervals
    idle, b = [], 0
    for ws, we in windows:
        t = ws
        while b < len(busy) and busy[b][0] < we:
            if busy[b][0] > t:
                idle.append([t, busy[b][0]])
            t = max(t, busy[b][1])
            b += 1
        if we > t:
            idle.append([t, we])
    dt.gaps = [(_span_at(spans, (s + e) / 2), e - s) for s, e in idle]
    for name in SPANS:
        within = _union((s, e) for n, s, e in spans if n == name)
        dt.idle_in_ns[name] = _length(_clip(idle, within))
    return dt


def reduce(path: str, device_ids, segments) -> TraceSummary:
    """Reduce the trace at ``path`` of ``len(segments)`` whole jobs, job k
    having run ``segments[k]`` segment programs, on the devices
    ``device_ids``."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(path)
    return reduce_profile(profile, device_ids, segments)


def reduce_profile(profile, device_ids, segments) -> TraceSummary:
    segments = [int(n) for n in segments]
    spans = _host_spans(profile)
    windows = _job_windows(spans, len(segments))
    planes = {}
    for plane in profile.planes:
        mo = _DEVICE_PLANE.match(plane.name)
        if mo and int(mo.group(1)) in set(device_ids):
            planes[int(mo.group(1))] = plane
    missing = sorted(set(device_ids) - set(planes))
    if missing:
        raise TraceMismatch(f"no trace plane for TPU device(s) {missing}")
    names = {r: set() for r in ROLES}
    ids = sorted(planes)
    per = [_device(planes[i], segments, windows, spans, names) for i in ids]
    for role, got in names.items():
        if len(got) != 1:
            raise TraceMismatch(f"the {role} programs carry {len(got)} "
                                f"names ({sorted(got)}); expected one")
    flat = {r: next(iter(v)) for r, v in names.items()}
    if len(set(flat.values())) != len(ROLES):
        raise TraceMismatch(f"the init, segment and finish programs do not "
                            f"carry three different names: {flat}")
    return TraceSummary(devices=ids, per_device=per,
                        window_s=_length(windows) * 1e-9,
                        n_jobs=len(segments), program_names=flat)
