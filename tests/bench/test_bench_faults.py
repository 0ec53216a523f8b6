"""A run whose timed path is broken underneath must read ``correct`` false.

Each test skips the harness's look for a chip, drives the rest of a run at
a CPU test's size, plants one fault in the program and checks the verdict:
a segment step that returns its state unchanged, half of each segment's
tasks left out, and one answer altered where it is produced; on the
four-chip cell, run on four CPU devices, also the exchange between chips
left out.
"""
import dataclasses
import json
import os

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _verdict(cell, devices):
    line, _ = run.run_cell(cell, devices, 2 ** 31 + 21, 0.1, False,
                           log=lambda msg: None)
    return line


def test_a_step_that_returns_its_state_unchanged(monkeypatch, tiny_cell,
                                                 cpu_devices):
    from repro.core.onesided import OneSidedBackend
    real = OneSidedBackend.make_segment_fns

    def frozen(self, *args):
        init, _, fin = real(self, *args)
        return init, (lambda carry, *segment: carry), fin

    monkeypatch.setattr(OneSidedBackend, "make_segment_fns", frozen)
    line = _verdict(tiny_cell("wc-wiki-1chip", task_size=1024,
                              push_cap=256), cpu_devices)
    assert line["correct"] is False
    assert line["checks"]["records_wrong"]["value"] > 0


def test_half_of_each_segment_left_out(monkeypatch, tiny_cell, cpu_devices):
    from repro.core import planner
    from repro.core.kv import KEY_SENTINEL
    real = planner.gather_segment

    def halved(source, plan, ids):
        tokens = real(source, plan, ids)
        tokens[:, tokens.shape[1] // 2:, :] = int(KEY_SENTINEL)
        return tokens

    monkeypatch.setattr(planner, "gather_segment", halved)
    line = _verdict(tiny_cell("hist-ratings-1chip", task_size=1024,
                              push_cap=256), cpu_devices)
    assert line["correct"] is False
    assert line["checks"]["records_wrong"]["value"] > 0


def test_an_answer_altered_where_it_is_produced(monkeypatch, tiny_cell,
                                                cpu_devices):
    from repro.core.job import JobHandle
    real = JobHandle._finish

    def altered(self):
        res = real(self)
        records = dict(res.records)
        key = next(iter(records))
        records[key] += 1
        return dataclasses.replace(res, records=records)

    monkeypatch.setattr(JobHandle, "_finish", altered)
    line = _verdict(tiny_cell("wc-wiki-1chip", task_size=1024,
                              push_cap=256), cpu_devices)
    assert line["correct"] is False
    # the warm-up job and each job of the window carry one wrong record
    assert line["checks"]["records_wrong"]["value"] == 1 + line["attempted"]


def test_each_fault_of_the_four_chip_cell(devices8):
    # one process on four CPU devices; each fault is planted, the cell
    # runs, and the fault is taken out again with the programs it built
    out = devices8(f"""
        import dataclasses, json, sys
        sys.path[:0] = [{ROOT!r}, {os.path.join(ROOT, "tests", "bench")!r}]
        import jax
        import jax.numpy as jnp
        from conftest import make_tiny_cell
        from bench import run
        from repro.core import onesided, planner
        from repro.core.job import JobHandle
        from repro.core.kv import KEY_SENTINEL
        from repro.core.onesided import OneSidedBackend
        from repro.core.registry import get_backend

        def frozen(real):
            def make(self, *args):
                init, _, fin = real(self, *args)
                return init, (lambda carry, *segment: carry), fin
            return make

        def halved(real):
            def gather(source, plan, ids):
                tokens = real(source, plan, ids)
                tokens[:, tokens.shape[1] // 2:, :] = int(KEY_SENTINEL)
                return tokens
            return gather

        def unsent(real):
            # no bytes cross chips: each rank gets back the block it
            # addressed to itself, and nothing from its peers
            def exchange(x, axis):
                own = jnp.arange(x.shape[0]) == jax.lax.axis_index(axis)
                own = own.reshape((-1,) + (1,) * (x.ndim - 1))
                return jnp.where(own, x, int(KEY_SENTINEL))
            return exchange

        def altered(real):
            def finish(self):
                res = real(self)
                records = dict(res.records)
                records[next(iter(records))] += 1
                return dataclasses.replace(res, records=records)
            return finish

        faults = {{
            "step returns its state": (OneSidedBackend, "make_segment_fns",
                                       frozen),
            "half of each segment": (planner, "gather_segment", halved),
            "no exchange between chips": (onesided, "all_to_all_blocks",
                                          unsent),
            "answer altered": (JobHandle, "_finish", altered),
        }}
        cell = make_tiny_cell("wc-wiki-4chip-bal", task_size=1024,
                              push_cap=256)
        got = {{}}
        for name, (owner, attr, plant) in faults.items():
            real = getattr(owner, attr)
            setattr(owner, attr, plant(real))
            try:
                line, _ = run.run_cell(cell, jax.devices()[:4], 2 ** 31 + 23,
                                       0.1, False, log=lambda msg: None)
            finally:
                setattr(owner, attr, real)
                get_backend("1s")._programs.clear()
            got[name] = [line["correct"],
                         line["checks"]["records_wrong"]["value"],
                         line["attempted"]]
        print(json.dumps(got))
    """, n_devices=4)
    got = json.loads(out.strip().splitlines()[-1])
    assert len(got) == 4
    for name, (correct, wrong, attempted) in got.items():
        assert correct is False and wrong > 0, name
    # the warm-up job and each job of the window carry one wrong record
    assert got["answer altered"][1] == 1 + got["answer altered"][2]
