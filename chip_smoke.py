"""Run the WordCount main path once on the TPU and check it against numpy.

    python chip_smoke.py             # one chip: "2s", then "1s"
    python chip_smoke.py --chips 4   # four chips, P=4, unbalanced grid:
                                     #   "2s", "1s" and "1s" + stealing

Both paths go through ``submit`` and the streaming ``SegmentFeed`` over a
seeded PUMA-like Zipf corpus (2^26 int32 tokens over a 2^22-word
vocabulary). Every job's records must equal the numpy oracle's, or the
script fails. It runs in one process, never falls back to the CPU, and
exits non-zero without a result line when JAX finds no TPU. The lines
before the last are diagnostics, not measurements; the last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_TOKENS = 2 ** 26
VOCAB = 2 ** 22
ZIPF_A = 1.1
TASK_SIZE = 4096
PUSH_CAP = 1024
SEGMENT = 8


def require_tpu():
    """The first device, which must be a TPU; raises SystemExit if not."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (first device: "
                         f"{dev.platform} {dev.device_kind})")
    return dev


def _run(backend, source, n_procs, stats, *, repeats=None,
         stealing=False):
    import jax
    from repro.core import JobConfig, WordCount, submit

    cfg = JobConfig(usecase=WordCount(vocab=VOCAB), backend=backend,
                    task_size=TASK_SIZE, push_cap=PUSH_CAP,
                    segment=SEGMENT, n_procs=n_procs, stealing=stealing)
    before = stats.snapshot()
    with submit(cfg, source, repeats=repeats) as handle:
        res = handle.result()
        devices = {d for d in handle.mesh.devices.flat}
        shards = {s.device for s in handle.carry.table.addressable_shards}
    if len(devices) != n_procs or shards != devices:
        raise RuntimeError(
            f"{backend}: mesh spans {len(devices)} devices and the window "
            f"lives on {len(shards)}, expected {n_procs}")
    after = stats.snapshot()
    comp = {k: after[k] - before[k] for k in after}
    label = backend + (" +steal" if stealing else "")
    st = handle.feed.stats
    print(f"{label}: wall_time {res.wall_time:.3f} s (compiles included), "
          f"{len(res.records)} records, steals {res.n_steals}, "
          f"imbalance {res.imbalance:.3f}")
    print(f"{label}: compiles {comp['compiles']} "
          f"({comp['compile_ms'] / 1e3:.1f} s), cache hits "
          f"{comp['cache_hits']}, misses {comp['cache_misses']}")
    print(f"{label}: feed segments {st.segments_built}, prefetch hits "
          f"{st.prefetch_hits}, max_live_bytes {st.max_live_bytes}")
    mem = jax.devices()[0].memory_stats() or {}
    print(f"{label}: device 0 peak_bytes_in_use "
          f"{mem.get('peak_bytes_in_use', 'not reported')}")
    return res


def smoke(chips: int) -> dict:
    """Run the phase for ``chips`` (1: "2s" then "1s" over every visible
    device; 4: the P=4 unbalanced comparison) and return the device
    record of the contract line. Raises on any mismatch."""
    import jax
    from repro.compile_cache import CompileStats
    from repro.core import wordcount_oracle
    from repro.data.corpus import imbalance_repeats
    from repro.data.source import ZipfSource, read_all

    dev = jax.devices()[0]
    count = jax.device_count()
    print(f"device: {dev.platform} {dev.device_kind}, count {count}")
    if chips == 4 and count != 4:
        raise SystemExit(f"chip_smoke: --chips 4 needs 4 devices, "
                         f"found {count}")
    source = ZipfSource(N_TOKENS, vocab=VOCAB, a=ZIPF_A, seed=0)
    t0 = time.perf_counter()
    oracle = wordcount_oracle(read_all(source), VOCAB)
    print(f"oracle: {len(oracle)} keys in {time.perf_counter() - t0:.1f} s "
          "(numpy, host)")
    stats = CompileStats()
    if chips == 1:
        runs = [_run("2s", source, count, stats),
                _run("1s", source, count, stats)]
    else:
        n_tasks = -(-N_TOKENS // TASK_SIZE)
        grid = imbalance_repeats(count, -(-n_tasks // count),
                                 mode="unbalanced")
        runs = [_run("2s", source, count, stats, repeats=grid),
                _run("1s", source, count, stats, repeats=grid),
                _run("1s", source, count, stats, repeats=grid,
                     stealing=True)]
    for res in runs:
        if res.records != oracle:
            raise AssertionError(f"{res.backend} records differ from the "
                                 "numpy oracle")
    print(f"records identical across {len(runs)} runs and the oracle")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": count}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: one-chip phase; 4: the P=4 stealing phase")
    args = ap.parse_args(argv)
    require_tpu()
    from repro import compile_cache
    print(f"compile cache: {compile_cache.enable()}")
    device = smoke(args.chips)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
