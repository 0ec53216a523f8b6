"""Streaming Word-Count — the paper's non-blocking I/O on a dataset that
is never fully resident.

A memory-mapped token file (stand-in for the paper's 300GB PUMA corpus)
is streamed segment-by-segment: the SegmentFeed reads the next segment's
tasks by file offset in a background thread while the engines compute
the current one. Peak host residency is O(segment); the result is
bit-identical to the in-memory run.

    PYTHONPATH=src python examples/streaming_wordcount.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import dataclasses
import tempfile

from repro import compile_cache
from repro.core import JobConfig, submit
from repro.core.usecases import WordCount
from repro.data.corpus import synth_corpus
from repro.data.source import ConcatSource, MmapTokenSource, ZipfSource


def main():
    compile_cache.enable()
    # a sharded on-disk corpus: two mmap'd part files + a lazy synthetic
    # tail, presented as one stream (nothing below materializes it)
    d = tempfile.mkdtemp()
    for i in range(2):
        synth_corpus(400_000, vocab=65_536, seed=i).tofile(
            os.path.join(d, f"part-{i}.bin"))
    source = ConcatSource([
        MmapTokenSource(os.path.join(d, "part-0.bin")),
        MmapTokenSource(os.path.join(d, "part-1.bin")),
        ZipfSource(200_000, vocab=65_536, seed=9),
    ])
    print(f"streaming {source.len_elements():,} tokens "
          f"({source.len_elements() * 4 / 2**20:.0f} MiB on disk/lazy)")

    cfg = JobConfig(usecase=WordCount(vocab=65_536), backend="1s",
                    task_size=4_096, push_cap=1_024, n_procs=8,
                    segment=4)
    handle = submit(cfg, source)           # no pre-shard, no full read
    while handle.step():
        pass                               # next segment prefetches behind
    result = handle.result()

    st = handle.feed.stats
    print(f"{result.n_tasks} tasks in {result.wall_time:.2f}s | "
          f"{st.prefetch_hits}/{st.segments_built} segments prefetched, "
          f"peak feed residency {st.max_live_bytes / 2**20:.2f} MiB "
          f"vs {st.bytes_read / 2**20:.0f} MiB streamed")

    # identical answer from the bulk-synchronous engine over the stream
    ref = submit(dataclasses.replace(cfg, backend="2s"), source).result()
    assert ref.records == result.records
    print(f"MR-1S == MR-2S over the stream: OK "
          f"({len(ref.records)} unique words)")


if __name__ == "__main__":
    main()
