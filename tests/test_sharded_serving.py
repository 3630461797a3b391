"""Sharded serving tests: tensor-parallel paged decode over the device
mesh must be EXACT — the two-pass (m, n) combine makes head- and
position-sharded attention bit-identical to the single-device path, so
every parity test here compares greedy tokens with ``==``, not allclose.

Mesh-shaped tests run in a subprocess (`_run`, the test_distributed.py
pattern): the fake-device count is locked at first jax init and the rest
of the suite needs the real 1-CPU world.  They are marked ``slow`` so
the fast lane is unaffected; the `serving-sharded` CI lane runs this
file without a marker filter (scripts/ci.sh sharded)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 4) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestRegistryShardKey:
    """In-process: the autotune-key extension is pure string logic."""

    def test_shards_suffix_is_backward_compatible(self):
        from repro.kernels import registry

        base = registry.cache_key("decode_paged", 64, 128, "float32", "cpu")
        assert registry.cache_key("decode_paged", 64, 128, "float32", "cpu",
                                  shards=1) == base
        sharded = registry.cache_key("decode_paged", 64, 128, "float32",
                                     "cpu", shards=2)
        assert sharded == base + "|s2"

    def test_tuned_entries_keyed_per_shard_count(self, tmp_path):
        from repro.kernels import registry

        p = str(tmp_path / "tune.json")
        registry.record_tuned("decode_paged", 64, 128, "float32", (8, 64),
                              backend="cpu", path=p, persist=False)
        registry.record_tuned("decode_paged", 64, 128, "float32", (4, 32),
                              backend="cpu", path=p, persist=False, shards=2)
        one = registry.lookup_tuned("decode_paged", 64, 128, "float32",
                                    backend="cpu", path=p)
        two = registry.lookup_tuned("decode_paged", 64, 128, "float32",
                                    backend="cpu", path=p, shards=2)
        assert one == (8, 64)
        assert two == (4, 32)


class TestShardingRules:
    @pytest.mark.slow
    def test_pool_specs_partition_rules(self):
        """Dense arena: KV-head axis over 'model'; page axis NEVER sharded;
        page tables/lengths replicated.  MLA pool: fully replicated (its TP
        lives in wkv_b).  Strip pool: slot axis over 'data' when divisible.
        Per-shard page budget scales by tp for dense, 1 for MLA."""
        out = _run("""
            import jax
            from jax.sharding import PartitionSpec as P
            from repro.configs import get_config
            from repro.serving import kv_cache
            from repro.distributed import sharding as sh
            from repro.launch.mesh import make_serving_mesh

            import dataclasses
            mesh = make_serving_mesh((2, 2))
            dense = get_config("qwen2.5-14b").reduced()
            mla = get_config("deepseek-v2-lite-16b").reduced()

            def replicated(s):
                return all(x is None for x in s)

            pool = kv_cache.init_paged_pool(dense, 2, 64, page_size=16)
            specs = sh.pool_specs(pool, dense, mesh)
            assert specs["kv"]["k"] == P(None, None, None, "model", None), \\
                specs["kv"]["k"]
            assert specs["kv"]["v"] == P(None, None, None, "model", None)
            assert replicated(specs["page_table"])
            assert replicated(specs["lengths"])

            mpool = kv_cache.init_paged_pool(mla, 2, 64, page_size=16)
            mspecs = sh.pool_specs(mpool, mla, mesh)
            for leaf in jax.tree.leaves(
                    mspecs, is_leaf=lambda x: isinstance(x, P)):
                assert replicated(leaf), leaf

            strip = kv_cache.init_slot_pool(dense, 2, 64)
            sspec = sh.pool_specs(strip, dense, mesh)["kv"]["k"]
            assert sspec[1] in ("data", ("data",)), sspec   # slot axis / dp
            assert sspec[3] == "model", sspec               # KV-head axis
            assert replicated(
                sh.pool_specs(strip, dense, mesh)["lengths"])

            assert sh.kv_shard_factor(dense, mesh) == 2
            assert sh.kv_shard_factor(mla, mesh) == 1
            # non-divisible head count falls back to replicated
            odd = dataclasses.replace(dense, n_kv_heads=3, n_heads=3)
            assert sh.kv_shard_factor(odd, mesh) == 1
            ospecs = sh.pool_specs(
                kv_cache.init_paged_pool(odd, 2, 64, page_size=16),
                odd, mesh)
            assert ospecs["kv"]["k"] == P(None, None, None, None, None)
            print("RULES_OK")
        """)
        assert "RULES_OK" in out


class TestShardedEngineParity:
    @pytest.mark.slow
    def test_dense_parity_prefix_and_budget(self):
        """Full engine on a (2,2) mesh: bit-identical greedy tokens, arena
        actually sharded over 'model', prefix-cache hits and allocator
        refcount invariant preserved, per-shard budget buys tp x pages,
        and a (1,1) mesh degenerates to the no-mesh tokens."""
        out = _run("""
            import numpy as np
            import jax
            from jax.sharding import PartitionSpec as P
            from repro.models import build_model
            from repro.serving.scheduler import Request
            from repro.launch.mesh import make_serving_mesh

            mesh = make_serving_mesh((2, 2))
            rng = np.random.default_rng(0)
            prompts = [tuple(rng.integers(1, 100,
                                          size=rng.integers(4, 14)).tolist())
                       for _ in range(6)]
            prompts[3] = prompts[0][:8] + (55, 56)   # shared-prefix pair

            def serve(mesh2):
                model = build_model("qwen2.5-14b", reduced=True)
                params = model.init(jax.random.PRNGKey(0))
                eng = model.serving_engine(params, slots=3, max_len=64,
                                           temperature=0.0, seed=2,
                                           page_size=8, mesh=mesh2)
                reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
                        for i, p in enumerate(prompts)]
                return [tuple(c.tokens) for c in eng.run(reqs)], eng

            t0, e0 = serve(None)
            t1, e1 = serve(mesh)
            assert t0 == t1, (t0, t1)
            assert (e1.pool["kv"]["k"].sharding.spec
                    == P(None, None, None, "model", None))
            tp = e1.throughput()
            assert tp["mesh_axes"] == {"data": 2, "model": 2}
            assert tp["kv_shards"] == 2
            # prefix sharing works identically under the mesh, and the
            # refcounted allocator stays consistent (no leak, no double
            # free): all non-free pages are held by the prefix index.
            assert e1.stats["prefix_hits"] == e0.stats["prefix_hits"] > 0
            assert (e1.allocator.free_pages + e1.prefix_cache.n_pages
                    == e1.allocator.usable_pages)

            model = build_model("qwen2.5-14b", reduced=True)
            params = model.init(jax.random.PRNGKey(0))
            budget = 1 << 20
            ea = model.serving_engine(params, memory_budget_bytes=budget,
                                      max_len=64, temperature=0.0,
                                      page_size=8)
            eb = model.serving_engine(params, memory_budget_bytes=budget,
                                      max_len=64, temperature=0.0,
                                      page_size=8, mesh=mesh)
            assert eb.allocator.usable_pages > ea.allocator.usable_pages

            t2, _ = serve(make_serving_mesh((1, 1)))
            assert t2 == t0
            print("DENSE_PARITY_OK")
        """)
        assert "DENSE_PARITY_OK" in out

    def test_launcher_inits_params_into_their_shardings(self):
        """``launch.serve.build_engine`` under a mesh initialises params
        straight into ``param_specs(fsdp=False)``, and serves the same
        greedy tokens as the single-device launcher."""
        out = _run("""
            import jax
            from repro.distributed import sharding
            from repro.launch.mesh import make_serving_mesh
            from repro.launch.serve import build_engine
            from repro.serving.scheduler import Request

            def serve(mesh):
                eng = build_engine("qwen2.5-14b", reduced=True, mesh=mesh,
                                   slots=2, max_len=40, temperature=0.0,
                                   page_size=8)
                reqs = [Request(rid=i, prompt=tuple(range(3, 12 + i)),
                                max_new_tokens=5) for i in range(3)]
                return [tuple(c.tokens) for c in eng.run(reqs)], eng

            mesh = make_serving_mesh((1, 4))
            t0, _ = serve(None)
            t1, eng = serve(mesh)
            assert t0 == t1, (t0, t1)
            want = sharding.named(sharding.param_specs(
                eng.model.init_shape(), eng.cfg, mesh, fsdp=False), mesh)
            got = jax.tree.map(lambda x: x.sharding, eng.params)
            assert jax.tree.leaves(got) == jax.tree.leaves(want)
            assert any(len(x.sharding.device_set) == 4
                       and not x.sharding.is_fully_replicated
                       for x in jax.tree.leaves(eng.params))
            print("SHARDED_INIT_OK")
        """)
        assert "SHARDED_INIT_OK" in out

    @pytest.mark.slow
    def test_paged_pool_made_in_its_shards(self):
        """Under a mesh each device makes only its own shard of the page
        arena: no whole arena lands on one device to be moved (a full
        arena there does not fit beside a 14B model's share of the
        weights).  An array made eagerly and then moved into its
        sharding would raise here."""
        out = _run("""
            import dataclasses
            import jax
            from repro.configs import get_config
            from repro.distributed import sharding as sh
            from repro.launch.mesh import make_serving_mesh
            from repro.serving import kv_cache

            mesh = make_serving_mesh((1, 4))
            cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                                      n_kv_heads=4)
            with jax.transfer_guard("disallow_explicit"):
                pool = kv_cache.init_paged_pool(cfg, 4, 64, 4, page_size=8,
                                                pages=20, mesh=mesh)
            want = sh.named(sh.pool_specs(pool, cfg, mesh), mesh)
            got = jax.tree.map(lambda x: x.sharding, pool)
            assert jax.tree.leaves(got) == jax.tree.leaves(want)
            k = pool["kv"]["k"]
            assert k.addressable_shards[0].data.shape[3] == \\
                cfg.n_kv_heads // 4
            print("POOL_IN_SHARDS_OK")
        """)
        assert "POOL_IN_SHARDS_OK" in out

    @pytest.mark.slow
    def test_strip_pool_made_in_its_shards(self):
        """The slot-strip pool goes through the same ``shard_pool``: made
        in its shards under one jit, never moved whole."""
        out = _run("""
            import dataclasses
            import functools
            import jax
            from repro.configs import get_config
            from repro.distributed import sharding as sh
            from repro.launch.mesh import make_serving_mesh
            from repro.serving import kv_cache

            mesh = make_serving_mesh((1, 4))
            cfg = dataclasses.replace(get_config("qwen2.5-14b").reduced(),
                                      n_kv_heads=4)
            make = functools.partial(kv_cache.init_slot_pool, cfg, 2, 64, 4)
            with jax.transfer_guard("disallow_explicit"):
                pool = kv_cache.shard_pool(make, cfg, mesh)
            want = sh.named(sh.pool_specs(pool, cfg, mesh), mesh)
            got = jax.tree.map(lambda x: x.sharding, pool)
            assert jax.tree.leaves(got) == jax.tree.leaves(want)
            assert not pool["kv"]["k"].sharding.is_fully_replicated
            print("STRIP_IN_SHARDS_OK")
        """)
        assert "STRIP_IN_SHARDS_OK" in out

    @pytest.mark.slow
    def test_mla_parity_replicated_pool(self):
        """MLA (latent-cache) family under the same mesh: pool replicated,
        params TP through wkv_b — tokens still bit-identical."""
        out = _run("""
            import numpy as np
            import jax
            from jax.sharding import PartitionSpec as P
            from repro.models import build_model
            from repro.serving.scheduler import Request
            from repro.launch.mesh import make_serving_mesh

            rng = np.random.default_rng(1)
            prompts = [tuple(rng.integers(1, 100,
                                          size=rng.integers(4, 12)).tolist())
                       for _ in range(4)]

            def serve(mesh2):
                model = build_model("deepseek-v2-lite-16b", reduced=True)
                params = model.init(jax.random.PRNGKey(0))
                eng = model.serving_engine(params, slots=2, max_len=64,
                                           temperature=0.0, seed=2,
                                           page_size=8, mesh=mesh2)
                reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
                        for i, p in enumerate(prompts)]
                return [tuple(c.tokens) for c in eng.run(reqs)], eng

            t0, _ = serve(None)
            t1, e1 = serve(make_serving_mesh((2, 2)))
            assert t0 == t1, (t0, t1)
            assert e1.throughput()["kv_shards"] == 1
            print("MLA_PARITY_OK")
        """)
        assert "MLA_PARITY_OK" in out

    @pytest.mark.slow
    def test_preemption_and_requeue_under_mesh(self):
        """Oversubscribed arena on the mesh: the younger request is
        preempted, requeued, recomputed — and still emits the exact tokens
        of an unsharded, unpreempted run."""
        out = _run("""
            import jax
            from repro.models import build_model
            from repro.serving.scheduler import Request
            from repro.launch.mesh import make_serving_mesh

            def serve(mesh2, pages):
                model = build_model("qwen2.5-14b", reduced=True)
                params = model.init(jax.random.PRNGKey(0))
                eng = model.serving_engine(params, slots=2, max_len=32,
                                           temperature=0.0, seed=2,
                                           page_size=8, pages=pages,
                                           mesh=mesh2)
                reqs = [Request(rid=i, prompt=tuple(range(1, 9)),
                                max_new_tokens=20) for i in range(2)]
                return [tuple(c.tokens) for c in eng.run(reqs)], eng

            mesh = make_serving_mesh((2, 2))
            t_sh, e_sh = serve(mesh, pages=7)
            assert e_sh.stats["preempted"] >= 1
            t_ref, e_ref = serve(None, pages=None)
            assert e_ref.stats["preempted"] == 0
            assert t_sh == t_ref, (t_sh, t_ref)
            assert (e_sh.allocator.free_pages + e_sh.prefix_cache.n_pages
                    == e_sh.allocator.usable_pages)
            print("PREEMPT_OK")
        """)
        assert "PREEMPT_OK" in out


class TestShardedKernelsAndSeqPar:
    @pytest.mark.slow
    def test_kernel_path_and_seq_parallel_ragged(self):
        """(a) Pallas decode kernels run INSIDE shard_map over the mesh
        (per-shard grid sees Hkv/tp heads) and agree with the unsharded
        kernel path on the greedy token.  (b) decode_seq_parallel no
        longer raises on the ragged path — it dispatches the position
        axis over 'model' and matches the baseline layout."""
        out = _run("""
            import dataclasses
            import numpy as np
            import jax, jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.models import build_model
            from repro.serving import engine, kv_cache
            from repro.distributed import autoshard, sharding as sh
            from repro.launch.mesh import make_serving_mesh

            model = build_model("qwen2.5-14b", reduced=True)
            cfg = model.cfg
            params = model.init(jax.random.PRNGKey(0))
            mesh = make_serving_mesh((2, 2))
            slots, max_len, page_size = 4, 64, 16

            rng = np.random.default_rng(0)
            T = 32
            cache = kv_cache.init_cache(cfg, 1, T)
            cache = jax.tree.map(
                lambda leaf: jnp.asarray(rng.standard_normal(leaf.shape),
                                         leaf.dtype), cache)
            page_row = np.full((kv_cache.pages_per_slot(max_len, page_size),),
                               kv_cache.TRASH_PAGE, np.int32)
            page_row[:2] = [1, 2]
            page_row = jnp.asarray(page_row)
            tokens = jnp.zeros((slots,), jnp.int32).at[0].set(7)

            def run(cfg2, mesh2):
                pool = kv_cache.init_paged_pool(
                    cfg2, slots, max_len, page_size=page_size, mesh=mesh2)
                pool = kv_cache.adopt_slot_paged(pool, cache, 0, T, page_row)
                def step(params, pool, tokens):
                    return engine.decode_step_ragged(params, pool, tokens,
                                                     cfg=cfg2)
                if mesh2 is None:
                    logits, _ = jax.jit(step)(params, pool, tokens)
                    return logits
                pspecs = sh.named(sh.pool_specs(pool, cfg2, mesh2), mesh2)
                rep = NamedSharding(mesh2, P())
                params_sh = jax.device_put(params, sh.named(
                    sh.param_specs(params, cfg2, mesh2, fsdp=False), mesh2))
                with autoshard.hints(mesh2):
                    logits, _ = jax.jit(
                        step, out_shardings=(rep, pspecs))(
                            params_sh, pool, tokens)
                return logits

            cfg_k = dataclasses.replace(cfg, use_kernels=True)
            l_ref = run(cfg_k, None)
            l_sh = run(cfg_k, mesh)
            assert int(jnp.argmax(l_ref[0])) == int(jnp.argmax(l_sh[0]))

            cfg_sp = dataclasses.replace(cfg, decode_seq_parallel=True)
            l_base = run(cfg, None)
            l_sp1 = run(cfg_sp, None)      # previously raised here
            l_sp2 = run(cfg_sp, mesh)
            assert int(jnp.argmax(l_base[0])) == int(jnp.argmax(l_sp1[0]))
            assert int(jnp.argmax(l_base[0])) == int(jnp.argmax(l_sp2[0]))
            print("KERNEL_SEQPAR_OK")
        """)
        assert "KERNEL_SEQPAR_OK" in out
