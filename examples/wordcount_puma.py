"""PUMA-style Word-Count under imbalance — the paper's §3 experiment at
container scale on the unified Job API, plus the engine-built vocabulary
feeding the tokenizer (the framework's ingest path).

    PYTHONPATH=src python examples/wordcount_puma.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

from repro import compile_cache
from repro.core import JobConfig, submit
from repro.core.usecases import WordCount
from repro.data.corpus import imbalance_repeats, synth_corpus
from repro.data.tokenizer import Vocab


def run_engine(tokens, backend, repeats, P=8):
    cfg = JobConfig(usecase=WordCount(vocab=65_536), backend=backend,
                    task_size=4_096, push_cap=1_024, n_procs=P)
    submit(cfg, tokens, repeats=repeats).result()     # compile + warm
    return submit(cfg, tokens, repeats=repeats).result()


def main():
    compile_cache.enable()
    P = 8
    tokens = synth_corpus(2_000_000, vocab=65_536, seed=0)
    T = (len(tokens) + 4_096 * P - 1) // (4_096 * P)

    print("=== balanced workload (paper Fig 4a/4b regime) ===")
    bal = imbalance_repeats(P, T, mode="balanced")
    res2 = run_engine(tokens, "2s", bal)
    res1 = run_engine(tokens, "1s", bal)
    print(f"MR-2S {res2.wall_time:.2f}s | MR-1S {res1.wall_time:.2f}s "
          f"({100 * (1 - res1.wall_time / res2.wall_time):+.1f}%)")

    print("\n=== unbalanced workload (hot ranks compute 8x — Fig 4c/4d) ===")
    unb = imbalance_repeats(P, T, mode="unbalanced", hot_factor=8,
                            hot_fraction=0.125)
    res2u = run_engine(tokens, "2s", unb)
    res1u = run_engine(tokens, "1s", unb)
    print(f"MR-2S {res2u.wall_time:.2f}s | MR-1S {res1u.wall_time:.2f}s "
          f"({100 * (1 - res1u.wall_time / res2u.wall_time):+.1f}%) "
          f"[imbalance {res1u.imbalance:.2f}]")
    assert res1u.records == res2u.records == res1.records

    # ingest path: the engine's counts build the LM tokenizer vocabulary
    counts = res1.records
    top = {f"word{k}".encode(): v for k, v in counts.items()}
    vocab = Vocab.from_counts(top, max_size=4_096)
    print(f"\nengine-built Vocab: size {vocab.size} "
          f"(top word id {max(counts, key=counts.get)}, "
          f"count {max(counts.values())})")


if __name__ == "__main__":
    main()
