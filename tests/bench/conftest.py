"""Shared helpers for the benchmark's CPU tests: the checkout's root and
``src`` on the import path, and cells cut to a size a test run holds."""
import dataclasses
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def make_tiny_cell(name, **kw):
    """The cell ``name`` of BENCHMARK.json at a CPU test's size."""
    from bench import cells
    return shrink(cells.load_cell(name), **kw)


def shrink(cell, tokens=1 << 16, vocab=1 << 10, **job):
    """``cell`` with fewer tokens, a smaller vocabulary where the use case
    counts words, and ``job`` fields replaced."""
    cfg = dict(cell.config, tokens_per_job=tokens,
               job=dict(cell.config["job"], **job))
    if cell.usecase_name == "wordcount":
        cfg["usecase"] = dict(cfg["usecase"], args={"vocab": vocab})
        cfg["token_ids"] = vocab
    return dataclasses.replace(cell, config=cfg)


@pytest.fixture
def tiny_cell():
    return make_tiny_cell


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices()[:1]
