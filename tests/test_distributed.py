"""Distribution-layer tests: sharding rules, collective parsing, dry-run
machinery on a small fake-device mesh (subprocess: device count is locked at
first jax init, and the rest of the suite needs the real 1-CPU world)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestShardingRules:
    def test_param_specs_cover_all_archs(self):
        """Every param of every arch gets a spec; no big-tensor fallback."""
        out = _run("""
            import jax, logging
            from repro.configs import ARCH_IDS, get_config
            from repro.models.model_zoo import Model
            from repro.distributed import sharding
            logging.basicConfig(level=logging.WARNING)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            for arch in ARCH_IDS:
                cfg = get_config(arch).reduced()
                import dataclasses
                # reduced dims: heads=4 etc; tp=4 divides
                m = Model(cfg, 4)
                specs = sharding.param_specs(m.init_shape(), cfg, mesh)
                n = len(jax.tree.leaves(specs,
                        is_leaf=lambda x: hasattr(x, '_normalized_spec')
                        or x.__class__.__name__ == 'PartitionSpec'))
                print(arch, n)
            print("ALL_OK")
        """)
        assert "ALL_OK" in out

    @pytest.mark.slow
    @pytest.mark.parametrize("kind", ["train", "decode", "prefill"])
    def test_cells_compile_on_small_mesh(self, kind):
        """The dry-run machinery end-to-end on a (2,4) mesh with reduced
        configs: lower + compile + analyses."""
        out = _run(f"""
            import jax
            from repro.configs.base import ShapeCell
            from repro.launch.lowering import build_cell, collective_bytes
            from repro.distributed import autoshard
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            cell = ShapeCell("t", 64, 16, "{kind}")
            with mesh, autoshard.hints(mesh):
                jitted, args = build_cell("granite-20b", cell, mesh,
                                          use_reduced=True, microbatches=1)
                compiled = jitted.lower(*args).compile()
            ma = compiled.memory_analysis()
            assert ma.temp_size_in_bytes >= 0
            coll = collective_bytes(compiled.as_text())
            print("COLL", coll["total"], coll["counts"])
            print("CELL_OK")
        """)
        assert "CELL_OK" in out
        if kind == "train":
            # gradient reduction must produce collectives
            assert "COLL 0" not in out

    def test_multipod_mesh_axes(self):
        out = _run("""
            from repro.launch.mesh import make_production_mesh
            m = make_production_mesh(multi_pod=True)
            assert m.axis_names == ("pod", "data", "model"), m.axis_names
            assert m.devices.shape == (2, 16, 16)
            m1 = make_production_mesh()
            assert m1.devices.shape == (16, 16)
            print("MESH_OK")
        """, devices=512)
        assert "MESH_OK" in out


class TestCollectiveParser:
    def test_parses_known_hlo(self):
        from repro.launch.lowering import collective_bytes

        hlo = """
  %ag = f32[16,512]{1,0} all-gather(f32[16,32]{1,0} %p), dimensions={1}
  %ar.1 = bf16[8,128]{1,0} all-reduce(bf16[8,128]{1,0} %x), to_apply=%sum
  %rs = (f32[4,32]{1,0}, f32[4,32]{1,0}) reduce-scatter(%a, %b), dimensions={0}
  %cp = f32[64]{0} collective-permute(f32[64]{0} %y), channel_id=3
  %a2a = f32[2,2]{1,0} all-to-all(f32[2,2]{1,0} %z), dimensions={0}
"""
        got = collective_bytes(hlo)
        assert got["counts"] == {"all-gather": 1, "all-reduce": 1,
                                 "reduce-scatter": 1,
                                 "collective-permute": 1, "all-to-all": 1}
        assert got["all-gather"] == 16 * 512 * 4
        assert got["all-reduce"] == 8 * 128 * 2
        assert got["reduce-scatter"] == 2 * 4 * 32 * 4
        assert got["total"] > 0

    def test_async_start_counted_once(self):
        from repro.launch.lowering import collective_bytes

        hlo = "%s = f32[128]{0} all-gather-start(f32[16]{0} %p)\n" \
              "%d = f32[128]{0} all-gather-done(%s)\n"
        got = collective_bytes(hlo)
        assert got["counts"] == {"all-gather": 1}


class TestRooflineMath:
    def test_analyze_cell(self, tmp_path):
        import sys
        sys.path.insert(0, REPO)
        from benchmarks.roofline import analyze_cell

        data = {
            "arch": "granite-20b", "cell": "train_4k", "skipped": False,
            "mesh": {"data": 16, "model": 16},
            "memory": {"argument_bytes": 2**30, "temp_bytes": 2**30,
                       "output_bytes": 0, "alias_bytes": 0},
            "scanned": {"flops": 1e15, "bytes": 1e12,
                        "collective_bytes": 1e10, "collective_counts": {}},
        }
        p = tmp_path / "x.json"
        p.write_text(json.dumps(data))
        r = analyze_cell(p)
        assert r["chips"] == 256
        # cost_analysis values are PER-DEVICE under SPMD (see roofline.py):
        # term divides by per-chip peak only
        assert abs(r["t_compute_s"] - 1e15 / 197e12) < 1e-9
        assert r["dominant"] in ("compute", "memory", "collective")
        assert r["useful_ratio"] > 0

    def test_model_flops_moe_uses_active(self):
        sys_path = sys.path
        from benchmarks.roofline import model_flops
        from repro.configs import get_config

        dense_equiv = model_flops("granite-20b", "train_4k")
        moe = model_flops("deepseek-v2-lite-16b", "train_4k")
        cfg = get_config("deepseek-v2-lite-16b")
        assert cfg.active_param_count() < cfg.param_count() / 3
        assert moe < dense_equiv          # 2.4B active < 20B


class TestSeqParallelDecode:
    @pytest.mark.slow
    def test_decode_seq_parallel_matches_baseline(self):
        """Sequence-parallel decode (cache seq over model + replicated
        q-heads) must produce identical logits to the baseline layout —
        exactness of the sharded-softmax combine."""
        out = _run("""
            import dataclasses
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_config
            from repro.models.model_zoo import Model
            from repro.distributed import sharding, autoshard
            from repro.serving import kv_cache, engine

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            base = get_config("qwen2.5-14b").reduced()
            base = dataclasses.replace(base, n_kv_heads=2, n_heads=4)
            results = {}
            for name, seq_par in (("base", False), ("seqpar", True)):
                cfg = dataclasses.replace(base, decode_seq_parallel=seq_par)
                m = Model(cfg, 4)
                params = m.init(jax.random.PRNGKey(0))
                cache = kv_cache.init_cache(cfg, 8, 32, 4)
                # fill cache with a short prompt via prefill
                toks = jax.random.randint(jax.random.PRNGKey(1), (8, 9), 0,
                                          cfg.vocab)
                _, cache = engine.prefill(params, toks[:, :-1], cfg=cfg,
                                          tp=4, max_len=32)
                with mesh, autoshard.hints(mesh):
                    cspecs = sharding.cache_specs(
                        jax.eval_shape(lambda: cache), cfg, mesh,
                        seq_shard=seq_par)
                    fn = jax.jit(lambda p, c, t, pos: engine.decode_step(
                        p, c, t, pos, cfg=cfg, tp=4)[0])
                    logits = fn(params, cache, toks[:, -1], jnp.int32(8))
                results[name] = np.asarray(logits[:, :cfg.vocab])
            np.testing.assert_allclose(results["base"], results["seqpar"],
                                       atol=2e-3)
            print("SEQPAR_OK")
        """)
        assert "SEQPAR_OK" in out
