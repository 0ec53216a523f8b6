"""Pytest gate for fleetlint (repro.analysis).

Three layers, mirroring the acceptance criteria:

  * the *shipping* matrix — every backend x use-case program and every
    pallas kernel must lint clean (in-process at P=1 here; the CI
    analysis job repeats it at P=8, and a slow subprocess test below
    covers P=8 from the suite too);
  * the *mutant corpus* — every rule has a known-bad seed that must
    fire and a near-miss twin that must stay completely quiet;
  * taint-lattice unit tests — targeted programs proving the abstract
    interpreter's fixpoints and control-dependence tracking are not
    vacuous.
"""
import dataclasses

import jax.numpy as jnp
import pytest
from jax import lax

from repro.analysis import corpus, lint, rules
from repro.analysis.taint import Finding
from repro.core.registry import get_backend, JobSpec
from repro.core.usecase import as_map_fn


# ---------------------------------------------------------------------------
# mutant corpus: every rule fires on its seed, never on the near-miss
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [m.name for m in corpus.MUTANTS])
def test_mutant_corpus(name):
    mutant = next(m for m in corpus.MUTANTS if m.name == name)
    got = corpus.run_mutant(mutant)
    if mutant.fires:
        assert any(f.rule == mutant.rule for f in got), \
            f"{name}: expected {mutant.rule} to fire, got {got}"
    else:
        assert got == [], f"{name}: near-miss must stay quiet, got {got}"


def test_every_rule_covered_by_corpus():
    rules_fired = {m.rule for m in corpus.MUTANTS if m.fires}
    rules_guarded = {m.rule for m in corpus.MUTANTS if not m.fires}
    expected = {"SPMD001", "SPMD002", "REP001",
                "PAL001", "PAL002", "PAL003"}
    assert rules_fired == expected
    assert rules_guarded == expected


# ---------------------------------------------------------------------------
# shipping matrix: every backend x use-case program lints clean
# ---------------------------------------------------------------------------

_MATRIX = [(b, c, s)
           for b in ("1s", "2s")
           for c, _ in corpus.SHIPPING_CASES
           for s in ((False, True) if b == "1s" else (False,))]


@pytest.mark.parametrize("bname,cname,stealing", _MATRIX)
def test_shipping_programs_clean(bname, cname, stealing):
    backend = get_backend(bname)
    usecase = dict(corpus.SHIPPING_CASES)[cname]
    mesh = corpus.procs_mesh()
    spec = JobSpec(vocab=usecase.window, task_size=8, push_cap=16,
                   n_procs=int(mesh.devices.size), segment=2,
                   stealing=stealing)
    for handle in backend.trace_handles(spec, as_map_fn(usecase), mesh,
                                        tag=f"{bname}/{cname}"):
        got = rules.check_program(handle)
        assert got == [], f"{handle.name}: {[str(f) for f in got]}"


@pytest.mark.parametrize("stealing", [False, True])
def test_coscheduled_engine_lints_clean(stealing):
    """The composite WorkDomain program ('1s' with coslots=2): the
    key-window offset and the psum-maintained carry.job_work row must
    satisfy the same replication contract as the solo engine."""
    backend = get_backend("1s")
    usecase = dict(corpus.SHIPPING_CASES)["wordcount"]
    mesh = corpus.procs_mesh()
    spec = JobSpec(vocab=usecase.window * 2, task_size=8, push_cap=16,
                   n_procs=int(mesh.devices.size), segment=2,
                   stealing=stealing, coslots=2, costride=2)
    handles = backend.trace_handles(spec, as_map_fn(usecase), mesh,
                                    tag="1s/wordcount+cosched")
    # the new carry row is part of the asserted replication contract
    assert any("carry.job_work" in h.replicated_out for h in handles)
    for handle in handles:
        got = rules.check_program(handle)
        assert got == [], f"{handle.name}: {[str(f) for f in got]}"


@pytest.mark.parametrize("kname", [k.name for k in
                                   corpus.shipping_kernels()])
def test_shipping_kernels_clean(kname):
    kc = next(k for k in corpus.shipping_kernels() if k.name == kname)
    got = rules.check_kernel(kc)
    assert got == [], f"{kname}: {[str(f) for f in got]}"


def test_analysis_not_vacuous_on_real_engine():
    """Over-asserting the contract on a *real* engine program must fire
    REP001 — proof the taint interpreter actually reaches the engine's
    outputs rather than trivially passing everything."""
    backend = get_backend("1s")
    usecase = dict(corpus.SHIPPING_CASES)["wordcount"]
    mesh = corpus.procs_mesh()
    spec = JobSpec(vocab=usecase.window, task_size=8, push_cap=16,
                   n_procs=int(mesh.devices.size), segment=2)
    _, _, fin = backend.trace_handles(spec, as_map_fn(usecase), mesh)
    # keys/values land on rank 0 only — claiming them replicated is wrong
    bogus = dataclasses.replace(
        fin, replicated_out=("keys", "values", "combine_overflow"))
    got = rules.check_program(bogus)
    assert any(f.rule == "REP001" and f.where in ("keys", "values")
               for f in got), got
    # ... while the shipped contract (overflow only) is clean
    assert rules.check_program(fin) == []


# ---------------------------------------------------------------------------
# taint-lattice unit tests
# ---------------------------------------------------------------------------

def _check(body, **kw):
    mesh = corpus.procs_mesh(1)
    handle = corpus._sm_handle("unit", body, mesh, **kw)
    return rules.check_program(handle)


def test_static_loop_preserves_replication():
    # fori_loop with static bounds lowers to scan: a replicated carry
    # stays replicated through the fixpoint
    def body(x):
        acc = lax.fori_loop(0, 4, lambda i, a: a + 1, x.sum())
        return acc[None]

    assert _check(body, replicated_in=("x0",),
                  replicated_out=("total",)) == []


def test_rank_dependent_trip_count_taints_carry():
    # fori_loop with a traced, axis_index-derived bound lowers to while:
    # the carry diverges with the trip count even if its updates do not
    def body(x):
        n = lax.axis_index("procs") + 1
        acc = lax.fori_loop(0, n, lambda i, a: a + 1, x.sum())
        return acc[None]

    got = _check(body, replicated_in=("x0",), replicated_out=("total",))
    assert [f.rule for f in got] == ["REP001"], got


def test_collective_under_rank_dependent_loop_fires_spmd002():
    def body(x):
        n = lax.axis_index("procs") + 1
        acc = lax.fori_loop(
            0, n, lambda i, a: a + lax.psum(jnp.int32(1), "procs"),
            x.sum())
        return acc[None]

    got = _check(body)
    assert any(f.rule == "SPMD002" for f in got), got


def test_psum_launders_taint_but_shuffle_does_not():
    def psum_body(x):
        return lax.psum(x.sum(), "procs")[None]

    def perm_body(x):
        return lax.ppermute(x.sum(), "procs", [(0, 0)])[None]

    assert _check(psum_body, replicated_out=("total",)) == []
    got = _check(perm_body, replicated_out=("total",))
    assert [f.rule for f in got] == ["REP001"], got


def test_varying_cond_output_is_varying():
    # both branches are pure, but a rank-divergent predicate makes the
    # *choice* rank-dependent — output must come out varying
    def body(x):
        pred = lax.axis_index("procs") == 0
        out = lax.cond(pred, lambda v: v + 1, lambda v: v - 1, x.sum())
        return out[None]

    got = _check(body, replicated_in=("x0",), replicated_out=("total",))
    assert [f.rule for f in got] == ["REP001"], got


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_selftest_passes():
    assert lint.main(["--selftest"]) == 0


def test_cli_kernels_clean_json(capsys):
    import json
    assert lint.main(["--kernels", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["checked"] == {"kernels": 4}
    assert payload["findings"] == []


def test_cli_waiver_matching():
    f = Finding("PAL002", "moe_dispatch", "output 0", "msg")
    assert lint._is_waived(f, [("PAL002", "moe")])
    assert lint._is_waived(f, [("PAL002", "output 0")])
    assert not lint._is_waived(f, [("PAL001", "moe")])
    assert not lint._is_waived(f, [("PAL002", "flash")])
    with pytest.raises(SystemExit):
        lint._parse_waivers(["PAL002"])


# ---------------------------------------------------------------------------
# full matrix at P=8 (what the CI analysis job sees)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleetlint_clean_at_p8(devices8):
    out = devices8("""
        from repro.analysis import lint
        rc = lint.main(["--all"])
        assert rc == 0, rc
        print("LINT-P8-CLEAN")
    """)
    assert "LINT-P8-CLEAN" in out
