"""Skew-aware Word-Count — beating hash partitioning on a Zipf corpus.

Natural text is Zipf-distributed, so the paper's static ``hash(key) % P``
ownership rule floods a few owners' windows. This example runs the same
job under all three partitioners (``repro/core/partition.py``), shows
the owner-load imbalance each one produces, and verifies the results
are record-identical — partitioning is placement, never semantics.

It also demonstrates the combine-overflow guard: an undersized
``combine_capacity`` used to silently return wrong counts; it now
raises ``CombineOverflowError`` with the dropped-record count.

    PYTHONPATH=src python examples/skewed_wordcount.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np

from repro import compile_cache
from repro.core import (CombineOverflowError, JobConfig, SampledPartitioner,
                        submit)
from repro.core.partition import owner_loads, sample_key_histogram
from repro.core.planner import plan_input, read_tasks
from repro.core.usecases import WordCount
from repro.data.source import ZipfSource

P, N, VOCAB, TASK = 8, 500_000, 65_536, 4_096


def main():
    compile_cache.enable()
    src = ZipfSource(N, vocab=VOCAB, a=1.8, seed=0)   # zipfy "natural text"
    uc = WordCount(vocab=VOCAB)

    base = None
    for part in ("hash", "sampled",
                 SampledPartitioner(split=True, split_threshold=0.05)):
        cfg = JobConfig(usecase=uc, backend="1s", task_size=TASK,
                        push_cap=1_024, n_procs=P, partitioner=part)
        with submit(cfg, src) as h:                   # handle is a CM:
            res = h.result()                          # feed never leaks
            # what would each rank receive under this owner map?
            plan = plan_input(N, TASK, P)
            hist = sample_key_histogram(
                lambda ids: read_tasks(src, plan, ids), plan, uc, 16)
            omap = np.asarray(h.carry.owner_map)[0]
            osplit = np.asarray(h.carry.owner_split)[0]
        load = owner_loads(hist, omap, osplit, P)
        print(f"{res.partitioner:<14} owner imbalance "
              f"{load.max() / load.mean():5.2f}   "
              f"split keys {res.n_split_keys:3d}   "
              f"records {len(res.records):,}")
        if base is None:
            base = res.records
        assert res.records == base                    # record-identical

    # --- the overflow guard ------------------------------------------------
    bad = JobConfig(usecase=uc, backend="1s", task_size=TASK,
                    push_cap=1_024, n_procs=P, combine_capacity=64)
    try:
        submit(bad, src).result()
    except CombineOverflowError as e:
        print(f"\ncombine_capacity=64 raises as it must: "
              f"{e.result.combine_overflow} records would have been "
              f"silently dropped pre-fix")


if __name__ == "__main__":
    main()
