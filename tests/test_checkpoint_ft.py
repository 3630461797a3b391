"""Checkpointing, crash-resume, elastic restore, fault-tolerance units."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ShapeCell
from repro.distributed import fault_tolerance as ft
from repro.models import build_model
from repro.training import train_state
from repro.training.trainer import Trainer, TrainerConfig


def _tiny_state(seed=0):
    m = build_model("granite-20b", reduced=True, n_layers=2)
    params = m.init(jax.random.PRNGKey(seed))
    return m, train_state.init_state(params)


class TestCheckpointer:
    def test_save_restore_roundtrip(self, tmp_path):
        m, state = _tiny_state()
        ck = Checkpointer(tmp_path)
        ck.save(7, state, blocking=True)
        assert ck.latest_step() == 7
        restored = ck.restore(7, jax.tree.map(np.zeros_like, state))
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_async_save(self, tmp_path):
        m, state = _tiny_state()
        ck = Checkpointer(tmp_path)
        ck.save(3, state, blocking=False)
        ck.wait()
        assert ck.latest_step() == 3

    def test_atomicity_no_partial_dirs(self, tmp_path):
        m, state = _tiny_state()
        ck = Checkpointer(tmp_path)
        ck.save(1, state, blocking=True)
        # only finalized dirs count; a stray tmp dir is invisible
        (tmp_path / "step_0000000002.tmp").mkdir()
        assert ck.latest_step() == 1

    def test_gc_keeps_latest(self, tmp_path):
        m, state = _tiny_state()
        ck = Checkpointer(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            ck.save(s, state, blocking=True)
        assert ck.steps() == [3, 4]

    def test_elastic_restore_different_mesh(self, tmp_path):
        """Save unsharded, restore onto a 1-device 'mesh' with specs — the
        code path a 512->256 chip restart takes."""
        m, state = _tiny_state()
        ck = Checkpointer(tmp_path)
        ck.save(5, state, blocking=True)
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((1,), ("model",), devices=jax.devices()[:1])

        pspecs = shd.param_specs(state.params, m.cfg, mesh)
        sspecs = train_state.state_specs(pspecs)
        step, restored = ck.restore_latest(state, mesh, sspecs)
        assert step == 5
        np.testing.assert_array_equal(
            np.asarray(restored.params["embed"]["table"]),
            np.asarray(state.params["embed"]["table"]))


class TestCrashResume:
    @pytest.mark.slow
    def test_resume_reproduces_uninterrupted_run(self, tmp_path):
        """Train 6 steps straight vs train 3 + crash + resume 3: identical
        final loss (exactly-once data + checkpointed optimizer state)."""
        cell = ShapeCell("t", 8, 8, "train")

        def run(steps, ckdir, resume):
            m = build_model("granite-20b", reduced=True, n_layers=2)
            t = Trainer(m, cell, TrainerConfig(
                steps=steps, checkpoint_every=3, checkpoint_dir=str(ckdir),
                log_every=100, peak_lr=1e-3, warmup=2))
            t.run()
            return t.metrics_history

        h1 = run(6, tmp_path / "a", False)
        # crash after 3 steps (simulated by a short run), then resume
        run(3, tmp_path / "b", False)
        h2 = run(6, tmp_path / "b", True)
        # steps 3..5 of both runs must match
        losses1 = {m["step"]: m["loss"] for m in h1}
        losses2 = {m["step"]: m["loss"] for m in h2}
        for s in (3, 4, 5):
            np.testing.assert_allclose(losses1[s], losses2[s], rtol=1e-5)


class TestFaultTolerance:
    def test_heartbeat_states(self):
        mon = ft.HeartbeatMonitor(["h0", "h1"], suspect_after_s=10,
                                  fail_after_s=20)
        mon.beat("h0", now=100.0)
        mon.beat("h1", now=100.0)
        assert mon.status(now=105.0) == {"h0": "healthy", "h1": "healthy"}
        mon.beat("h0", now=112.0)
        assert mon.status(now=115.0)["h1"] == "suspect"   # 15s > 10s
        assert mon.status(now=115.0)["h0"] == "healthy"
        assert mon.failed_hosts(now=125.0) == ["h1"]      # 25s > 20s
        assert mon.should_restart(now=125.0)

    def test_straggler_detection(self):
        t = ft.StepTimer(window=20, straggler_factor=2.0)
        for _ in range(10):
            assert not t.record(1.0)
        assert t.record(5.0)          # 5x median
        assert not t.record(1.1)

    def test_restart_backoff(self):
        p = ft.RestartPolicy(max_restarts=3, base_backoff_s=1.0)
        assert p.next_backoff() == 1.0
        assert p.next_backoff() == 2.0
        assert p.next_backoff() == 4.0
        assert p.next_backoff() is None

    @pytest.mark.parametrize("chips,expect", [
        (512, (32, 16)), (511, (16, 16)), (256, (16, 16)),
        (240, (8, 16)), (16, (1, 16)), (15, None)])
    def test_elastic_plan(self, chips, expect):
        assert ft.elastic_plan(chips, model_parallel=16) == expect


class TestGradCompression:
    def test_bf16_roundtrip_close(self):
        from repro.distributed import compression

        g = {"w": jnp.linspace(-1, 1, 1000, dtype=jnp.float32)}
        out = compression.decompress_bf16(compression.compress_bf16(g))
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                                   atol=4e-3)

    def test_int8_error_feedback_reduces_bias(self):
        from repro.distributed import compression

        key = jax.random.PRNGKey(0)
        g = {"w": jax.random.normal(key, (512,)) * 0.01}
        ef = compression.init_error_feedback(g)
        # accumulate the same gradient many times: with EF the mean
        # dequantized grad converges to the true one
        total = jnp.zeros((512,))
        n = 50
        for _ in range(n):
            payload, ef = compression.compress_int8(g, ef)
            total = total + compression.decompress_int8(payload)["w"]
        np.testing.assert_allclose(np.asarray(total / n),
                                   np.asarray(g["w"]), atol=1e-4)
