"""Shared benchmark infrastructure.

Two measurement modes, both honest about this container:

1. **Real runs** — the engines execute end-to-end on 8 host devices and we
   record wall time. On ONE oversubscribed CPU core, device threads are
   work-conserving: a fast rank's idle time is absorbed by the slow rank's
   compute, so phase-overlap gains physically cannot appear in wall time
   here. Real runs therefore validate correctness + schedule overheads.

2. **Calibrated lockstep schedule model** — per-op costs (map at repeat r,
   bucketize, window fold, chunk all_to_all, combine) are *measured* on
   this machine one-at-a-time (no contention), then composed into the exact
   SPMD lockstep makespan of each engine's schedule. This mirrors how the
   TPU executes the same programs (collectives synchronize; XLA overlaps
   async pushes with compute) and is what EXPERIMENTS.md compares against
   the paper's Fig 4. The model also takes TPU-parameterized constants
   (bytes / ICI bw) for the production-scale projections.

Subprocess isolation: every real engine run happens in a fresh process with
its own ``--xla_force_host_platform_device_count`` (the main process never
touches jax device state — same rule as the dry-run).
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
RESULTS = os.path.join(REPO, "results")


def run_py(code: str, n_devices: int = 8, timeout: int = 580) -> str:
    prelude = (f"import os\nos.environ['XLA_FLAGS'] = "
               f"'--xla_force_host_platform_device_count={n_devices}'\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c",
                           prelude + textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-3000:]}")
    return proc.stdout


def save_json(name: str, obj) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return path


def stream_triad_gbps(mb: float = 256.0, reps: int = 5) -> float:
    """Measured machine memory bandwidth, STREAM-triad style.

    ``a = b + s * c`` over preallocated arrays large enough to defeat the
    caches; counts 3 reads + 2 writes per element (numpy materializes the
    multiply into ``a`` first), best-of-``reps``. This is the roofline
    ceiling fig12 states achieved-bandwidth fractions against — measured
    here, on this machine, not quoted from a spec sheet.
    """
    n = int(mb * 2**20 / 8 / 3)          # three resident arrays of float64
    a = np.empty(n)
    b = np.random.default_rng(0).random(n)
    c = np.random.default_rng(1).random(n)
    s = 1.000001
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.multiply(c, s, out=a)
        np.add(a, b, out=a)
        best = min(best, time.perf_counter() - t0)
    return 5 * n * 8 / best / 1e9


# ---------------------------------------------------------------------------
# per-op cost calibration (measured, no contention)
# ---------------------------------------------------------------------------

CALIB_CODE = """
import json, time
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from repro.core.kv import (bucketize, local_reduce, local_reduce_repeated,
                           mix32, KEY_SENTINEL)
from repro.core.windows import DenseWindow
from repro.core.usecase import as_map_fn
from repro.core.usecases import WordCount

TASK = {task_size}
P = {n_procs}
CAP = {push_cap}
VOCAB = {vocab}

def timeit(fn, *args, n=20):
    jax.block_until_ready(fn(*args))          # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n

rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, VOCAB, TASK), jnp.int32)
map_fn = as_map_fn(WordCount(vocab=VOCAB))

def make_task(r):
    # the full per-task sender work at repeat r: map + (repeated) local
    # reduce + bucketize — exactly the engines' phase I+II
    @jax.jit
    def f(t):
        keys, vals = map_fn(t, jnp.int32(0), jnp.int32(r))
        uk, uv = local_reduce_repeated(keys, vals, keys.shape[0],
                                       jnp.int32(r))
        return bucketize(uk, uv, P, CAP)
    return f

t_task1 = timeit(make_task(1), toks)
t_task8 = timeit(make_task(8), toks)
t_task_per_rep = max((t_task8 - t_task1) / 7, 0.0)

win = jnp.zeros((VOCAB,), jnp.int32)
ck = jnp.asarray(rng.integers(0, VOCAB, (P, CAP)), jnp.int32)
cv = jnp.ones((P, CAP), jnp.int32)
@jax.jit
def fold(w, k, v):
    return DenseWindow(w).put(k.reshape(-1), v.reshape(-1)).table
t_fold = timeit(fold, win, ck, cv)

# combine: one merge level at window W
W = VOCAB
ka = jnp.sort(jnp.asarray(rng.integers(0, VOCAB, W), jnp.int32))
va = jnp.ones((W,), jnp.int32)
from repro.core.kv import merge_sorted
@jax.jit
def merge(k1, v1, k2, v2):
    return merge_sorted(k1, v1, k2, v2, W)
t_merge = timeit(merge, ka, va, ka, va)

print(json.dumps(dict(t_task1=t_task1, t_task_per_rep=t_task_per_rep,
                      t_fold=t_fold, t_merge=t_merge,
                      chunk_bytes=float(P * CAP * 8))))
"""

A2A_CODE = """
import json, time
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.distributed.mesh import local_mesh

n = {n_procs}
CAP = {push_cap}
mesh = local_mesh((n,), ("procs",))

def measure(cap):
    def body(x):
        x = x[0]
        return lax.all_to_all(x, "procs", 0, 0, tiled=False)[None]
    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("procs"),),
                           out_specs=P("procs")))
    x = jnp.ones((n, n, cap, 2), jnp.int32)
    jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    for _ in range(10):
        out = fn(x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / 10

# two sizes -> per-op latency (alpha) + per-chunk-bytes slope (beta):
# the bulk MPI_Alltoallv pays alpha once for T chunks; the chunked
# one-sided pushes pay it every round
t1 = measure(CAP)
t8 = measure(CAP * 8)
beta = max((t8 - t1) / 7, 0.0)
alpha = max(t1 - beta, 0.0)
print(json.dumps(dict(t_a2a=t1, t_a2a_lat=alpha, t_a2a_byte=beta,
                      bytes_per_dev=float(n * CAP * 8))))
"""


def calibrate(task_size=4096, n_procs=8, push_cap=1024, vocab=65536) -> dict:
    out = run_py(CALIB_CODE.format(task_size=task_size, n_procs=n_procs,
                                   push_cap=push_cap, vocab=vocab),
                 n_devices=1)
    costs = json.loads(out.strip().splitlines()[-1])
    out2 = run_py(A2A_CODE.format(n_procs=n_procs, push_cap=push_cap),
                  n_devices=n_procs)
    costs.update(json.loads(out2.strip().splitlines()[-1]))
    return costs


# ---------------------------------------------------------------------------
# lockstep schedule simulator
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Costs:
    """Per-op seconds. Build from ``calibrate()`` (CPU) or TPU constants."""
    t_task1: float           # full per-task sender work at repeat=1
                             #   (map + local reduce + bucketize)
    t_task_per_rep: float    # extra seconds per compute-repeat
    t_fold: float            # fold one (P, cap) chunk into the window
    t_merge: float           # one combine merge level
    t_a2a_lat: float         # all_to_all per-op latency (alpha)
    t_a2a_byte: float        # all_to_all per-chunk transfer time (beta)
    comm_overlap: bool = True   # async collectives overlap compute (TPU)
    t_io: float = 0.0        # input retrieval per task (paper: dominates);
                             #   prefetched → overlaps compute in BOTH
                             #   engines, so it adds as max(io, compute)
    t_fetch: float = 0.0     # 1s+steal only: the per-step task-fetch
                             #   all_to_all (a claimed task's input is
                             #   served by global id before map can run,
                             #   so it sits ON the critical path — the
                             #   steal scheduler's honest overhead)

    def task_time(self, rep: np.ndarray) -> np.ndarray:
        comp = self.t_task1 + self.t_task_per_rep * np.maximum(rep - 1, 0)
        return np.maximum(comp, self.t_io)

    @property
    def t_a2a_chunk(self) -> float:
        return self.t_a2a_lat + self.t_a2a_byte

    def t_a2a_bulk(self, T: int) -> float:
        """MPI_Alltoallv of T chunks: latency paid once (the collective's
        efficiency edge the paper observes on balanced / large-P runs)."""
        return self.t_a2a_lat + self.t_a2a_byte * T

    @staticmethod
    def from_calibration(c: dict, comm_overlap=True, t_io=0.0) -> Costs:
        return Costs(c["t_task1"], c["t_task_per_rep"], c["t_fold"],
                     c["t_merge"], c["t_a2a_lat"], c["t_a2a_byte"],
                     comm_overlap=comm_overlap, t_io=t_io)

    @staticmethod
    def tpu_like(task_mb=64.0, push_cap=1024, n_procs=256,
                 comm_overlap=True, storage_gbps=2.0) -> Costs:
        """First-principles v5e-flavoured constants (DESIGN.md §9): task
        compute is memory-bound over the task bytes; input retrieval from
        parallel storage at ``storage_gbps``/rank dominates (the paper's
        word-count regime: "execution mostly depends on the time required
        to retrieve the input"); chunk a2a over 50 GB/s ICI links."""
        hbm = 819e9
        link = 50e9
        task_bytes = task_mb * 2 ** 20
        chunk_bytes = n_procs * push_cap * 8
        return Costs(
            t_task1=task_bytes * 9 / hbm,        # hash + sort passes
            t_task_per_rep=task_bytes * 7 / hbm,
            t_fold=chunk_bytes * 2 / hbm,
            t_merge=chunk_bytes * 2 / hbm,
            t_a2a_lat=5e-6,
            t_a2a_byte=chunk_bytes / link,
            comm_overlap=comm_overlap,
            t_io=task_bytes / (storage_gbps * 1e9))


def simulate(costs: Costs, repeats: np.ndarray, backend: str,
             want_timeline: bool = False):
    """Exact lockstep makespan of one engine schedule.

    repeats: (P, T) compute-repeat factors. Returns seconds
    (+ optional per-round timeline [(t0, t1, phase, per_proc_busy)]).
    """
    P, T = repeats.shape
    mt = costs.task_time(repeats)                 # (P, T)
    n_levels = int(np.ceil(np.log2(max(P, 2))))
    timeline: list = []
    t = 0.0

    def round_(dur: float, phase: str, busy):
        nonlocal t
        if want_timeline:
            timeline.append((t, t + dur, phase, np.asarray(busy).tolist()))
        t += dur

    if backend == "2s":
        # 2S's map scan has NO collectives — devices run their whole task
        # list decoupled and sync only at the bulk a2a: the map phase is
        # max_p(Σ_t), not Σ_t max_p. (Equal for rank-hot imbalance;
        # kinder to 2S under random task-level imbalance.)
        per_proc = mt.sum(axis=1)
        round_(float(per_proc.max()), "map", per_proc)
        # bulk shuffle (T chunks of bytes in one fused a2a — latency
        # amortized, the collective's edge), then the reduce spike (fold T
        # chunks), then combine
        round_(costs.t_a2a_bulk(T), "shuffle",
               np.full(P, costs.t_a2a_bulk(T)))
        round_(costs.t_fold * T, "reduce", np.full(P, costs.t_fold * T))
        round_(costs.t_merge * n_levels, "combine",
               np.full(P, costs.t_merge * n_levels))
    elif backend in ("1s", "1s+steal"):
        # chunked push: fold of chunk k-1 overlaps the push of chunk k;
        # the a2a itself overlaps next round's compute when async — but
        # pays its latency every round (1S's downside on small tasks).
        # With stealing, the per-step schedule is the one the claim
        # function actually realizes (heavy tasks migrate to ranks that
        # ran ahead, packing them into the same lockstep rounds), and
        # every round additionally pays the task-fetch a2a up front.
        if backend == "1s+steal":
            from repro.core.steal import steal_schedule
            ids = np.arange(repeats.size, dtype=np.int32).reshape(P, T)
            mt = costs.task_time(steal_schedule(ids, repeats).exec_reps)
        for k in range(T):
            busy = mt[:, k] + costs.t_fold
            comp = busy.max()
            dur = max(comp, costs.t_a2a_chunk) if costs.comm_overlap \
                else comp + costs.t_a2a_chunk
            if backend == "1s+steal":
                dur += costs.t_fetch
            round_(dur, "map+reduce", busy)
        round_(costs.t_fold, "drain", np.full(P, costs.t_fold))
        round_(costs.t_merge * n_levels, "combine",
               np.full(P, costs.t_merge * n_levels))
    else:
        raise ValueError(backend)
    return (t, timeline) if want_timeline else t


def speedup(costs: Costs, repeats: np.ndarray) -> dict[str, float]:
    t2 = simulate(costs, repeats, "2s")
    t1 = simulate(costs, repeats, "1s")
    return {"t_2s": t2, "t_1s": t1, "improvement_pct": 100 * (1 - t1 / t2)}
