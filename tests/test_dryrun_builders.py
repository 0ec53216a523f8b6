"""Dry-run builder integration on a small production-like mesh.

The 512-device sweep runs out-of-process (results/dryrun); here the same
builders lower + compile smoke-sized cells on a (2,2) mesh in a subprocess
— exercising input_specs, sharding assembly, train/prefill/decode program
construction and the §Perf variants end to end inside the test suite.
"""


def test_builders_compile_all_kinds(devices8):
    out = devices8("""
        import dataclasses
        import jax
        from repro.config import SHAPES, MeshConfig, ShapeConfig
        from repro.configs.registry import get_smoke_config
        from repro.distributed.mesh import local_mesh
        from repro.launch import dryrun as dr
        from repro.launch.hlo_stats import collective_bytes

        mesh = local_mesh((2, 2), ("data", "model"))
        mesh_cfg = MeshConfig((2, 2), ("data", "model"))

        for arch, kinds in [("olmo-1b", ("train", "prefill", "decode")),
                            ("llama4-maverick-400b-a17b", ("train",
                                                           "decode")),
                            ("mamba2-780m", ("decode",))]:
            cfg = get_smoke_config(arch)
            for kind in kinds:
                shape = ShapeConfig("t", 64, 4, kind)
                fn, args, in_sh, _ = dr.build_cell(cfg, shape, mesh,
                                                   mesh_cfg)
                compiled = jax.jit(fn, in_shardings=in_sh).lower(
                    *args).compile()
                txt = compiled.as_text()
                cb = collective_bytes(txt)
                ca = compiled.cost_analysis()
                assert ca.get("flops", 0) > 0
                print(arch, kind, "ok", int(cb.get("total", 0)))

        # §Perf variants lower too (flat_dp train; serve decode)
        cfg = get_smoke_config("olmo-1b")
        shape = ShapeConfig("t", 64, 4, "train")
        fn, args, in_sh, _ = dr.build_train(cfg, shape, mesh, mesh_cfg,
                                            microbatch=4, remat="dots",
                                            sharding="flat_dp")
        jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        print("flat_dp ok")
        cfg = dataclasses.replace(
            get_smoke_config("llama4-maverick-400b-a17b"),
            expert_tp_axis="data")
        shape = ShapeConfig("t", 64, 4, "decode")
        fn, args, in_sh, _ = dr.build_decode(cfg, shape, mesh, mesh_cfg,
                                             sharding="serve")
        jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
        print("serve_ep ok")
        print("BUILDERS-OK")
    """, n_devices=4, timeout=560)
    assert "BUILDERS-OK" in out
