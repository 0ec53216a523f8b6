"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: for each cell
the full train_step / serve_step / prefill program is lowered with explicit
in_shardings onto the production mesh and compiled; memory_analysis shows it
fits, cost_analysis + HLO collective parsing feed §Roofline.

Roofline calibration (DESIGN.md §9): XLA cost analysis counts scan bodies
once, so per cell we additionally lower *unrolled* reduced-depth variants —
(nb=1,A=1), (nb=2,A=1) and (nb=1,A=2) where nb = scanned super-blocks and
A = grad-accum steps — and extrapolate exactly (the program is affine in
both trip counts):

    cost(NB, A) = cost(1,1) + (A-1)·dA + A·(NB-1)·dL
    dL = cost(2,1) - cost(1,1);  dA = cost(1,2) - cost(1,1)

Usage:
    python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    python -m repro.launch.dryrun --arch all [--multipod] [--no-calibrate]
"""
# The VERY FIRST lines — before ANY other import — jax locks device count
# on first init.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import time
import traceback
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import SHAPES, MeshConfig, ModelConfig, ShapeConfig
from repro.configs.registry import ARCH_IDS, cell_status, get_config
from repro.launch import specs as specs_mod
from repro.launch.hlo_stats import collective_bytes
from repro.launch.mesh import make_production_mesh, mesh_config
from repro.models.transformer import init_model, prefill
from repro.serve.engine import make_serve_step
from repro.train.train_step import init_train_state, make_train_step


# ---------------------------------------------------------------------------
# program builders — each returns (fn, args_abstract, in_shardings)
# ---------------------------------------------------------------------------

def _params_abstract(cfg: ModelConfig):
    return jax.eval_shape(partial(init_model, cfg), jax.random.key(0))


# §Perf hillclimb variants (EXPERIMENTS.md §Perf). ``model`` overrides go
# into ModelConfig; ``remat``/``microbatch`` into the run; ``sharding``
# picks the distributed/sharding.py rule variant.
VARIANTS = {
    "base": {},
    "dots": dict(remat="dots"),
    "dots_a1": dict(remat="dots", microbatch="full"),
    "flatdp": dict(remat="dots", microbatch="full", sharding="flat_dp"),
    "disp2s": dict(remat="dots", microbatch="full",
                   model=dict(dispatch_mode="2s")),
    "disp1s": dict(remat="dots", microbatch="full",
                   model=dict(dispatch_mode="1s")),
    "serve_ep": dict(sharding="serve", model=dict(expert_tp_axis="data")),
    # remat=none is feasible once A=1 shrinks live activations (per-device
    # block boundary ~34-42 MB × n_blocks ≈ 1 GB)
    "flatdp_nr": dict(remat="none", microbatch="full", sharding="flat_dp"),
    "a1_nr": dict(remat="none", microbatch="full"),
    # pipeline across pods (multipod only): stages replace cross-pod DP —
    # DCN carries activation permutes instead of gradient all-reduce
    "pp_pod": dict(pipeline=True),
}


def build_train_pp(cfg: ModelConfig, shape: ShapeConfig, mesh,
                   mesh_cfg: MeshConfig, *, n_microbatches: int = 8):
    """GPipe over the pod axis; flat data-FSDP inside each stage.

    Mesh: the 512 devices re-axised to (data=256, pod=2) with the physical
    pod split preserved (devices.reshape(2,256).T). Two XLA partial-manual
    partitioner workarounds, both isolated empirically (see EXPERIMENTS
    §Perf PP note): the manual axis must be minor-most, and the embedding
    table must not be vocab-sharded (the gather resharding CHECK-fails in
    spmd_partitioner_util.cc:504) — embed/lm_head are replicated instead.

    Scope note (recorded in EXPERIMENTS §Perf): at 512 devices XLA can
    partition the PP **forward+loss** program (lowered here — its
    collective schedule is the artifact of interest: cross-pod traffic
    becomes activation permutes); the backward trips a second partitioner
    CHECK ("Invalid binary instruction opcode copy"). The full PP train
    step (loss+grads+update, bit-matching the non-PP path) is validated at
    small scale in tests/test_pipeline.py.
    """
    import numpy as _np
    from jax.sharding import Mesh, NamedSharding
    from repro.distributed.pipeline import gpipe_loss_fn
    n_pods = mesh_cfg.shape[0]
    devs = _np.asarray(mesh.devices).reshape(n_pods, -1)
    n_data = devs.shape[1]
    mesh = Mesh(devs.T, ("data", "pod"))
    run = specs_mod.make_run(cfg, shape, mesh_cfg)

    def fn(params, batch):
        return gpipe_loss_fn(cfg, params, batch, mesh=mesh,
                             n_microbatches=n_microbatches, remat="dots")

    params_abs = _params_abstract(cfg)

    def _fsdp(dims, start):
        spec = [None] * len(dims)
        for i in range(start, len(dims)):
            if dims[i] % n_data == 0:
                spec[i] = "data"
                break
        return spec

    def spec_of(path, leaf):
        keys = [str(getattr(p, "key", p)) for p in path]
        if keys[-1] in ("embed_tokens", "lm_head"):
            return P(*([None] * len(leaf.shape)))
        if "blocks" in keys:
            return P("pod", *_fsdp(leaf.shape, 1)[1:])
        return P(*_fsdp(leaf.shape, 0))

    p_specs = jax.tree_util.tree_map_with_path(spec_of, params_abs)
    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), p_specs)
    batch_abs = specs_mod.input_specs(cfg, shape)
    batch_sh = jax.tree.map(
        lambda l: NamedSharding(mesh, P("data", *([None] *
                                                  (len(l.shape) - 1)))),
        batch_abs)
    return fn, (params_abs, batch_abs), (p_sh, batch_sh), run


def build_train(cfg: ModelConfig, shape: ShapeConfig, mesh,
                mesh_cfg: MeshConfig, *, unroll=False, microbatch=0,
                remat=None, sharding="default"):
    run = specs_mod.make_run(cfg, shape, mesh_cfg, microbatch=microbatch)
    if remat:
        run = dataclasses.replace(
            run, train=dataclasses.replace(run.train, remat_policy=remat))
    dp = specs_mod.dp_entry_for(shape, mesh_cfg, sharding)
    fn = make_train_step(cfg, run, mesh=mesh, dp_entry=dp, unroll=unroll)
    params_abs = _params_abstract(cfg)
    state_abs = jax.eval_shape(
        partial(init_train_state, cfg, run.train), params_abs)
    state_sh = specs_mod.state_shardings(cfg, mesh, mesh_cfg, state_abs,
                                         sharding)
    batch_abs = specs_mod.input_specs(cfg, shape)
    batch_sh = specs_mod.batch_shardings(cfg, shape, mesh, mesh_cfg,
                                         batch_abs, sharding)
    return fn, (state_abs, batch_abs), (state_sh, batch_sh), run


def build_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh,
                  mesh_cfg: MeshConfig, *, unroll=False,
                  sharding="default", **_):
    dp = specs_mod.dp_entry_for(shape, mesh_cfg)
    fn = partial(prefill, cfg, mesh=mesh, dp_entry=dp, unroll=unroll)
    params_abs = _params_abstract(cfg)
    p_sh = specs_mod.params_shardings(cfg, mesh, mesh_cfg, params_abs,
                                      sharding)
    batch_abs = specs_mod.input_specs(cfg, shape)
    batch_sh = specs_mod.batch_shardings(cfg, shape, mesh, mesh_cfg,
                                         batch_abs)
    return fn, (params_abs, batch_abs), (p_sh, batch_sh), None


def build_decode(cfg: ModelConfig, shape: ShapeConfig, mesh,
                 mesh_cfg: MeshConfig, *, unroll=False,
                 sharding="default", **_):
    dp = specs_mod.dp_entry_for(shape, mesh_cfg)
    fn = make_serve_step(cfg, mesh=mesh, dp_entry=dp, unroll=unroll)
    params_abs = _params_abstract(cfg)
    p_sh = specs_mod.params_shardings(cfg, mesh, mesh_cfg, params_abs,
                                      sharding)
    cache_abs, tok_abs, t_abs = specs_mod.decode_input_specs(cfg, shape)
    cache_sh = specs_mod.cache_shardings(cfg, shape, mesh, mesh_cfg,
                                         cache_abs)
    tok_sh = NamedSharding(mesh, P(dp, None))
    t_sh = NamedSharding(mesh, P())
    return fn, (params_abs, cache_abs, tok_abs, t_abs), \
        (p_sh, cache_sh, tok_sh, t_sh), None


def build_cell(cfg, shape, mesh, mesh_cfg, *, unroll=False, microbatch=0,
               remat=None, sharding="default"):
    if shape.kind == "train":
        return build_train(cfg, shape, mesh, mesh_cfg, unroll=unroll,
                           microbatch=microbatch, remat=remat,
                           sharding=sharding)
    if shape.kind == "prefill":
        return build_prefill(cfg, shape, mesh, mesh_cfg, unroll=unroll,
                             sharding=sharding)
    return build_decode(cfg, shape, mesh, mesh_cfg, unroll=unroll,
                        sharding=sharding)


# ---------------------------------------------------------------------------
# lower + compile + measure
# ---------------------------------------------------------------------------

def _numeric(d) -> dict[str, float]:
    try:
        return {k: float(v) for k, v in dict(d).items()
                if isinstance(v, (int, float))}
    except Exception:
        return {}


def lower_compile(fn, args_abs, in_sh, *, want_text=True) -> dict[str, Any]:
    t0 = time.time()
    lowered = jax.jit(fn, in_shardings=in_sh).lower(*args_abs)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec: dict[str, Any] = {
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2)}
    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = _numeric(ma) if ma is not None else None
        if not rec["memory_analysis"] and ma is not None:
            rec["memory_analysis"] = {
                k: float(getattr(ma, k)) for k in dir(ma)
                if not k.startswith("_")
                and isinstance(getattr(ma, k, None), (int, float))}
    except Exception as e:           # CPU backend may not implement it
        rec["memory_analysis"] = {"error": str(e)[:200]}
    try:
        ca = compiled.cost_analysis()
        rec["cost_analysis"] = {
            k: float(v) for k, v in (ca or {}).items()
            if isinstance(v, (int, float)) and (
                "flops" in k or "bytes" in k or "utilization" not in k)}
    except Exception as e:
        rec["cost_analysis"] = {"error": str(e)[:200]}
    if want_text:
        txt = compiled.as_text()
        rec["collectives"] = collective_bytes(txt)
        rec["hlo_chars"] = len(txt)
    return rec


def _reduced_cfg(cfg: ModelConfig, nb: int) -> ModelConfig:
    return dataclasses.replace(
        cfg, n_layers=cfg.first_k_dense + nb * cfg.block_pattern)


def _extrapolate(c11, c21, c12, NB: int, A: int, keys=("flops",)):
    """Affine extrapolation of numeric dicts (see module docstring)."""
    out = {}
    for k in keys:
        a = c11.get(k, 0.0)
        dL = c21.get(k, 0.0) - a
        dA = (c12.get(k, 0.0) - a) if c12 else 0.0
        out[k] = a + (A - 1) * dA + A * (NB - 1) * dL
    return out


def calibrate(cfg: ModelConfig, shape: ShapeConfig, mesh,
              mesh_cfg: MeshConfig, *, microbatch=0, remat=None,
              sharding="default") -> dict[str, Any]:
    """Unrolled reduced-depth lowerings → exact full-program roofline terms."""
    run = specs_mod.make_run(cfg, shape, mesh_cfg, microbatch=microbatch)
    mb = run.resolved_microbatch()
    A_full = run.grad_accum_steps
    NB_full = cfg.n_scan_blocks

    def one(nb: int, A: int):
        c = _reduced_cfg(cfg, nb)
        if shape.kind == "train":
            sh = dataclasses.replace(shape, global_batch=mb * A)
            fn, args, in_sh, _ = build_train(c, sh, mesh, mesh_cfg,
                                             unroll=True, microbatch=mb,
                                             remat=remat, sharding=sharding)
        else:
            fn, args, in_sh, _ = build_cell(c, shape, mesh, mesh_cfg,
                                            unroll=True, sharding=sharding)
        return lower_compile(fn, args, in_sh)

    r11 = one(1, 1)
    r21 = one(2, 1)
    r12 = one(1, 2) if (shape.kind == "train" and A_full > 1) else None

    keys = ("flops", "bytes accessed")
    c11 = r11["cost_analysis"]; c21 = r21["cost_analysis"]
    c12 = r12["cost_analysis"] if r12 else None
    cost = _extrapolate(c11, c21, c12, NB_full, A_full, keys)

    ckeys = set(r11["collectives"]) | set(r21["collectives"])
    col11 = r11["collectives"]; col21 = r21["collectives"]
    col12 = r12["collectives"] if r12 else None
    coll = _extrapolate(col11, col21, col12 or {}, NB_full, A_full,
                        tuple(ckeys))
    return {
        "microbatch": mb, "grad_accum": A_full, "scan_blocks": NB_full,
        "flops_per_device": cost.get("flops", 0.0),
        "hbm_bytes_per_device": cost.get("bytes accessed", 0.0),
        "collective_bytes_per_device": coll,
        "variants": {"nb1_a1": r11, "nb2_a1": r21,
                     **({"nb1_a2": r12} if r12 else {})},
    }


# ---------------------------------------------------------------------------
# cell driver
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             do_calibrate: bool, out_dir: str,
             variant: str = "base") -> dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    v = dict(VARIANTS[variant])
    cfg = dataclasses.replace(cfg, **v.pop("model", {}))
    mb = v.pop("microbatch", 0)
    if mb == "full":
        mb = shape.global_batch
    remat = v.pop("remat", None)
    sharding = v.pop("sharding", "default")
    pipeline = v.pop("pipeline", False)
    mesh_name = "multipod" if multi_pod else "singlepod"
    rec: dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "variant": variant}
    runnable, why = cell_status(cfg, shape)
    if not runnable:
        rec.update(status="skip", reason=why)
        return _emit(rec, out_dir)
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_cfg = mesh_config(multi_pod=multi_pod)
        if pipeline:
            assert multi_pod and shape.kind == "train", \
                "pp_pod variant: multipod train cells only"
            fn, args, in_sh, run = build_train_pp(cfg, shape, mesh,
                                                  mesh_cfg)
        else:
            fn, args, in_sh, run = build_cell(cfg, shape, mesh, mesh_cfg,
                                              microbatch=mb, remat=remat,
                                              sharding=sharding)
        rec["full"] = lower_compile(fn, args, in_sh)
        if run is not None:
            rec["microbatch"] = run.resolved_microbatch()
            rec["grad_accum"] = run.grad_accum_steps
        if do_calibrate and not multi_pod:
            rec["calibration"] = calibrate(cfg, shape, mesh, mesh_cfg,
                                           microbatch=mb, remat=remat,
                                           sharding=sharding)
        rec["status"] = "ok"
    except Exception:
        rec["status"] = "fail"
        rec["error"] = traceback.format_exc()[-4000:]
    return _emit(rec, out_dir)


def _emit(rec, out_dir):
    os.makedirs(out_dir, exist_ok=True)
    suffix = "" if rec.get("variant", "base") == "base" \
        else f"__{rec['variant']}"
    path = os.path.join(
        out_dir,
        f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec["status"]
    extra = ""
    if status == "ok":
        ca = rec["full"].get("cost_analysis", {})
        extra = (f" flops/dev={ca.get('flops', 0):.3e}"
                 f" compile={rec['full']['compile_s']}s")
    print(f"[dryrun] {rec['arch']} × {rec['shape']} × {rec['mesh']}:"
          f" {status}{extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--variant", default="base", choices=sorted(VARIANTS))
    ap.add_argument("--out-dir", default="results/dryrun")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multipod]
    n_fail = 0
    for arch in archs:
        for s in shapes:
            for mp in meshes:
                rec = run_cell(arch, s, multi_pod=mp,
                               do_calibrate=not args.no_calibrate,
                               out_dir=args.out_dir, variant=args.variant)
                n_fail += rec["status"] == "fail"
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
