"""The replication taint lattice and its abstract interpreter.

Two-point lattice over SPMD values, per mesh axis set:

    REPLICATED  ⊑  VARYING

A value is REPLICATED when every rank holds the same bits (the paper's
windows-synchronized state: claim cursors, owner maps, overflow totals);
VARYING otherwise. The interpreter walks a jaxpr with standard abstract
interpretation: join = max, monotone transfer functions per primitive,
fixpoints for ``scan``/``while`` carries, and a *control taint* that
tracks whether execution itself is rank-divergent (a ``cond`` predicate
or ``while`` trip count derived from ``axis_index``).

Three findings originate here:

  * SPMD001 — a collective names a mesh axis outside the program's
    allowed set (the engine contract is ``("procs",)``);
  * SPMD002 — a collective is reachable under rank-divergent control
    flow (the SPMD deadlock analog of an unmatched one-sided epoch);
  * REP001  — an output the backend asserts replicated is derived from
    rank-varying data without an intervening collective (e.g. a dropped
    ``psum`` on a progress row).

Soundness notes: unknown primitives conservatively join their inputs and
any hidden sub-jaxpr is still scanned for collectives; ``psum`` (and
friends) only launder taint when reducing over an *allowed named* axis —
positional-axes psum (from vmap) is a plain local op.
"""
from __future__ import annotations

import dataclasses

from jax.extend import core as jcore

from repro.analysis.tracer import subjaxprs, where_of

REPLICATED = 0
VARYING = 1

# full-axis reductions: every rank receives the identical result (a
# reduction of a rank-varying operand traces as the ``*_invariant`` form)
REPLICATING = frozenset({"psum", "psum_invariant", "pmax", "pmin",
                         "all_gather", "all_gather_invariant"})
# rank-dependent data movement: ranks receive different slices
SHUFFLING = frozenset({"all_to_all", "ppermute", "pgather", "pscatter"})
COLLECTIVES = REPLICATING | SHUFFLING

# higher-order primitives whose single sub-jaxpr maps invars/outvars 1:1
# onto the equation's own — taint passes straight through
_TRANSPARENT = frozenset({
    "jit", "shard_map", "closed_call", "core_call", "remat",
    "checkpoint", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr",
})


@dataclasses.dataclass(frozen=True)
class Finding:
    """One analyzer finding, keyed by rule id + jaxpr provenance."""
    rule: str
    program: str
    where: str
    message: str

    def __str__(self) -> str:
        return f"[{self.rule}] {self.program} @ {self.where}: {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def named_axes(eqn) -> tuple:
    """The *named* mesh axes a collective operates over (ints from vmap
    positional reductions are dropped)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


class TaintAnalyzer:
    """Abstract interpreter over one program's jaxpr."""

    def __init__(self, program: str, allowed_axes):
        self.program = program
        self.allowed = frozenset(allowed_axes)
        self.findings: list[Finding] = []
        self._seen: set = set()

    # -- findings ----------------------------------------------------------

    def _emit(self, rule: str, where: str, message: str) -> None:
        key = (rule, where, message)
        if key not in self._seen:      # fixpoint passes revisit equations
            self._seen.add(key)
            self.findings.append(Finding(rule, self.program, where, message))

    # -- interpretation ----------------------------------------------------

    def run(self, closed: jcore.ClosedJaxpr, in_taints: list) -> list:
        """Propagate input taints through the whole program; returns the
        flat output taints (findings accumulate on ``self.findings``)."""
        return self._eval(closed, list(in_taints), REPLICATED)

    def _eval(self, jaxpr, in_taints: list, control: int) -> list:
        if isinstance(jaxpr, jcore.ClosedJaxpr):
            # closed-over consts are host constants: replicated
            jaxpr = jaxpr.jaxpr
        env: dict = {}

        def read(atom) -> int:
            if isinstance(atom, jcore.Literal):
                return REPLICATED
            return env.get(atom, REPLICATED)

        for v in jaxpr.constvars:
            env[v] = REPLICATED
        for v, t in zip(jaxpr.invars, in_taints):
            env[v] = t
        for eqn in jaxpr.eqns:
            ts = [read(x) for x in eqn.invars]
            outs = self._transfer(eqn, ts, control)
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
        return [read(x) for x in jaxpr.outvars]

    def _transfer(self, eqn, ts: list, control: int) -> list:
        name = eqn.primitive.name
        n_out = len(eqn.outvars)
        join_in = max(ts, default=REPLICATED)

        if name in COLLECTIVES:
            axes = named_axes(eqn)
            bad = sorted(a for a in axes if a not in self.allowed)
            if bad:
                self._emit(
                    "SPMD001", where_of(eqn),
                    f"collective '{name}' over mesh axis {bad} outside "
                    f"the allowed set {sorted(self.allowed)}")
            if control == VARYING:
                self._emit(
                    "SPMD002", where_of(eqn),
                    f"collective '{name}' reachable under rank-divergent "
                    "control flow (predicate tainted by axis_index) — "
                    "ranks would disagree on whether to enter it")
            if not axes:               # positional-only (vmapped) reduce
                return [join_in] * n_out
            if name in REPLICATING:
                return [REPLICATED] * n_out
            return [VARYING] * n_out

        if name == "axis_index":
            return [VARYING] * n_out

        if name == "cond":             # also `switch` (multi-branch cond)
            pred, args = ts[0], ts[1:]
            child = max(control, pred)
            outs = [REPLICATED] * n_out
            for branch in eqn.params["branches"]:
                bouts = self._eval(branch, list(args), child)
                outs = [max(a, b) for a, b in zip(outs, bouts)]
            # rank-divergent predicate -> outputs are control-dependent
            return [max(o, pred) for o in outs]

        if name == "while":
            p = eqn.params
            cn, bn = p["cond_nconsts"], p["body_nconsts"]
            cond_c, body_c = ts[:cn], ts[cn:cn + bn]
            carry = list(ts[cn + bn:])
            pred = REPLICATED
            for _ in range(len(carry) + 2):    # monotone: must stabilize
                pred = max(pred, self._eval(
                    p["cond_jaxpr"], cond_c + carry,
                    max(control, pred))[0])
                child = max(control, pred)
                new = self._eval(p["body_jaxpr"], body_c + carry, child)
                # rank-divergent trip count -> carries diverge too
                merged = [max(a, b, pred) for a, b in zip(carry, new)]
                if merged == carry:
                    break
                carry = merged
            return carry

        if name == "scan":             # static trip count: no divergence
            p = eqn.params
            nc, nk = p["num_consts"], p["num_carry"]
            consts, xs = ts[:nc], ts[nc + nk:]
            carry = list(ts[nc:nc + nk])
            ys = [REPLICATED] * (n_out - nk)
            for _ in range(len(carry) + 2):
                outs = self._eval(p["jaxpr"], consts + carry + xs, control)
                ys = [max(a, b) for a, b in zip(ys, outs[nk:])]
                merged = [max(a, b) for a, b in zip(carry, outs[:nk])]
                if merged == carry:
                    break
                carry = merged
            return carry + ys

        if name in _TRANSPARENT:
            sub = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            if sub is not None:
                inner = sub.jaxpr if isinstance(sub, jcore.ClosedJaxpr) \
                    else sub
                if len(inner.invars) == len(ts):
                    outs = self._eval(sub, ts, control)
                    if len(outs) == n_out:
                        return outs

        # unknown primitive: conservatively join inputs; still sweep any
        # hidden sub-jaxpr (e.g. a pallas kernel body) so a collective
        # buried inside cannot escape SPMD001/SPMD002
        for sub in subjaxprs(eqn.params):
            self._eval(sub, [join_in] * len(sub.invars), control)
        return [join_in] * n_out


def analyze_handle(handle, closed: jcore.ClosedJaxpr) -> list:
    """Run the taint interpreter over a traced ProgramHandle and check
    its replication contract. Returns all findings (SPMD001/2 + REP001).
    """
    analyzer = TaintAnalyzer(handle.name, handle.allowed_axes)
    replicated_in = frozenset(handle.replicated_in)
    in_taints = [REPLICATED if p in replicated_in else VARYING
                 for p in handle.arg_paths]
    out_taints = analyzer.run(closed, in_taints)
    replicated_out = frozenset(handle.replicated_out)
    for path, taint in zip(handle.out_paths, out_taints):
        if path in replicated_out and taint == VARYING:
            analyzer._emit(
                "REP001", path,
                f"output '{path}' is asserted replicated but derives "
                "from rank-varying data with no intervening collective "
                "(e.g. a dropped psum)")
    return analyzer.findings
