"""Quickstart — the paper's Listing 1 on the unified Job API, in 20 lines.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

from repro import compile_cache
from repro.core import JobConfig, submit
from repro.core.usecases import WordCount
from repro.data.corpus import synth_corpus


def main():
    compile_cache.enable()
    tokens = synth_corpus(500_000, vocab=65_536, seed=0)

    # paper Listing 1, redesigned: declare the use-case + backend, submit.
    # A raw array is auto-wrapped in an ArraySource and streamed through
    # the same SegmentFeed as any DataSource (mmap files, lazy corpora —
    # see examples/streaming_wordcount.py); nothing is pre-sharded.
    cfg = JobConfig(usecase=WordCount(vocab=65_536), backend="1s",
                    task_size=4_096, push_cap=1_024, n_procs=8)
    result = submit(cfg, tokens).result()
    print("top-10 words (id\tcount):")
    for k, v in sorted(result.records.items(), key=lambda kv: -kv[1])[:10]:
        print(f"{k}\t{v}")
    print(f"\n{result.n_tasks} tasks over {len(result.tasks_per_rank)} "
          f"ranks in {result.wall_time:.2f}s "
          f"(imbalance {result.imbalance:.2f})")

    # the bulk-synchronous reference (Hoefler et al.) gives the same answer
    import dataclasses
    ref = submit(dataclasses.replace(cfg, backend="2s"), tokens).result()
    assert ref.records == result.records
    print(f"MR-1S == MR-2S result: OK ({len(ref.records)} unique words)")


if __name__ == "__main__":
    main()
