"""A run whose timed path is broken underneath must read ``correct`` false.

Each test skips the harness's look for a chip, drives the rest of a run at
a CPU test's size, plants one fault in the program and checks the verdict:
a segment step that returns its state unchanged, half of each segment's
tasks left out, and one answer altered where it is produced. The cells run
on one chip, so there is no exchange between chips to leave out.
"""
import dataclasses

from bench import run


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
