"""Find a cell's parts by name.

``BENCHMARK.json`` at the root of the checkout names every cell
(``workloads``), configuration, traffic mix and metric. Each part lives in
a file of its own, found from its name alone:

* a configuration: the ``file`` its ``configs`` entry gives
  (``bench/configs/<config>.json``);
* a traffic mix: ``bench/traffic/<traffic>.json``, read by the one
  generator in ``bench/generate.py``;
* a plain reference: ``bench/oracles/<usecase>.py``, for the use case the
  configuration names;
* a per-layer metric: ``bench/metrics/<metric>.py``, whose ``read(run)``
  returns the value, or None where the run has nothing to read. A metric
  entry with a ``workloads`` list is read only in the cells it names; one
  without is read in every cell;
* a chip's peaks: ``bench/peaks.json``, keyed by JAX's ``device_kind``.

A later cell, configuration, mix or metric is a new file and a new entry
in ``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                # the configuration file as JSON
    traffic: dict               # the traffic mix file as JSON
    end_to_end: tuple           # Metric, in BENCHMARK.json's order
    per_layer: tuple

    @property
    def usecase_name(self) -> str:
        return self.config["usecase"]["name"]

    @property
    def tokens_per_job(self) -> int:
        return int(self.config["tokens_per_job"])


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _metrics(entries: list, cell: str, cells: dict) -> tuple:
    """The metrics of ``entries`` that ``cell`` reports: those with no
    ``workloads`` list and those whose list names it. A list that names
    no cell of ``cells`` is an error."""
    for m in entries:
        unknown = sorted(set(m.get("workloads", ())) - set(cells))
        if unknown:
            raise ValueError(f"metric {m['name']} names no such workload: "
                             f"{', '.join(unknown)}")
    return tuple(Metric(m["name"], m["unit"]) for m in entries
                 if cell in m.get("workloads", (cell,)))


def load_cell(name: str) -> Cell:
    """The cell ``name`` of BENCHMARK.json with its
    configuration and traffic mix read; raises KeyError for an unknown
    name."""
    spec = load_benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    with open(ROOT / configs[w["config"]]["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic=traffic,
                end_to_end=_metrics(spec["end_to_end"], name, cells),
                per_layer=_metrics(spec["per_layer"], name, cells))


def _load_module(path: Path, label: str):
    if not path.is_file():
        raise FileNotFoundError(f"{label}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{label}_{path.stem}".replace("-", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def oracle(usecase: str):
    """The plain reference module for a use case."""
    return _load_module(BENCH / "oracles" / f"{usecase}.py", "oracle")


def metric_reader(name: str):
    """The ``read(run)`` function of a per-layer metric."""
    return _load_module(BENCH / "metrics" / f"{name}.py", "metric").read


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``. A kind the
    table does not hold is an error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"bench/peaks.json has no peaks for device kind "
                       f"{device_kind!r} (has: {', '.join(sorted(table))})")
    return table[device_kind]
