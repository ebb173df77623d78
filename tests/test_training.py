"""Training substrate tests: optimizer, checkpointing, fault tolerance,
stream-fed loop, gradient compression."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_stream import consumer_lm
from repro.models import transformer as T
from repro.training.checkpoint import CheckpointManager
from repro.training.data import StreamBatcher, SyntheticBatcher
from repro.training.ft import FailureInjector, StragglerMonitor, elastic_plan
from repro.training.optimizer import AdamW, adamw_init, adamw_update
from repro.training.steps import jit_train_step
from repro.training.train_loop import TrainLoop, TrainLoopConfig


def tiny_lm():
    return consumer_lm().replace(n_layers=2, d_model=64, n_heads=4,
                                 n_kv_heads=2, head_dim=16, d_ff=128,
                                 vocab_size=512, loss_chunk=16)


def make_state(cfg, seed=0):
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    return params, adamw_init(params)


class TestOptimizer:
    def test_descends_on_fixed_batch(self):
        cfg = tiny_lm()
        params, opt_state = make_state(cfg)
        opt = AdamW(lr=3e-3, warmup_steps=2, total_steps=60)
        step = jit_train_step(cfg, opt, mesh=None, donate=False)
        batch = next(iter(SyntheticBatcher(4, 32, cfg.vocab_size)))
        losses = []
        for _ in range(25):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] * 0.7, f"no descent: {losses[::6]}"

    def test_grad_clip(self):
        cfg = tiny_lm()
        params, opt_state = make_state(cfg)
        g = jax.tree.map(lambda p: jnp.full(p.shape, 100.0, jnp.float32),
                         params)
        opt = AdamW(grad_clip=1.0)
        _, _, stats = adamw_update(opt, g, opt_state, params)
        assert float(stats["grad_norm"]) > 1.0  # recorded pre-clip


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        cfg = tiny_lm()
        params, opt_state = make_state(cfg)
        mgr = CheckpointManager(tmp_path, keep=2)
        mgr.save(3, {"params": params, "opt": opt_state})
        state = mgr.restore({"params": params, "opt": opt_state})
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(state["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gc_and_latest(self, tmp_path):
        cfg = tiny_lm()
        params, _ = make_state(cfg)
        mgr = CheckpointManager(tmp_path, keep=2)
        for s in (1, 2, 3, 4):
            mgr.save(s, {"p": params})
        assert mgr.steps() == [3, 4]
        assert mgr.latest_step() == 4

    def test_async_save(self, tmp_path):
        cfg = tiny_lm()
        params, _ = make_state(cfg)
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, {"p": params}, blocking=False)
        mgr.wait()
        assert mgr.latest_step() == 1

    def test_shape_mismatch_rejected(self, tmp_path):
        mgr = CheckpointManager(tmp_path)
        mgr.save(1, {"w": jnp.zeros((4, 4))})
        with pytest.raises(ValueError):
            mgr.restore({"w": jnp.zeros((8, 8))})


class TestFaultTolerance:
    def _loop(self, tmp_path, injector=None, steps=30, seed=0):
        cfg = tiny_lm()
        params, opt_state = make_state(cfg, seed)
        opt = AdamW(lr=1e-3, warmup_steps=2, total_steps=steps)
        step = jit_train_step(cfg, opt, mesh=None, donate=False)
        batches = iter(SyntheticBatcher(4, 32, cfg.vocab_size, seed=seed))
        mgr = CheckpointManager(tmp_path, keep=3)
        return TrainLoop(step, params, opt_state, batches, mgr,
                         TrainLoopConfig(total_steps=steps,
                                         checkpoint_every=10,
                                         async_checkpoint=False),
                         injector=injector)

    def test_failure_recovery_completes(self, tmp_path):
        inj = FailureInjector({17: "process-death", 23: "device-loss"})
        loop = self._loop(tmp_path / "a", injector=inj)
        summary = loop.run()
        assert summary["final_step"] == 30
        assert summary["restarts"] == 2
        assert np.isfinite(summary["final_loss"])

    def test_straggler_monitor(self):
        mon = StragglerMonitor(tolerance=2.0, window=10)
        for i in range(10):
            mon.observe(i, 0.1)
        assert mon.observe(10, 0.5) is True
        assert mon.observe(11, 0.11) is False
        assert mon.summary()["mitigated"] == 1

    def test_elastic_plan(self):
        # lose a host: 512 -> 480 chips, model axis 16 stays
        shape, per_shard = elastic_plan(480, (2, 16, 16),
                                        ("pod", "data", "model"), 256)
        assert shape[2] == 16
        assert 256 % per_shard == 0
        assert shape[0] * shape[1] * shape[2] <= 480
        with pytest.raises(ValueError):
            elastic_plan(8, (16, 16), ("data", "model"), 256)

    def test_nan_quarantine(self, tmp_path):
        cfg = tiny_lm()
        params, opt_state = make_state(cfg)

        calls = {"n": 0}

        def poisoned_step(p, o, b):
            calls["n"] += 1
            loss = jnp.float32(np.nan if calls["n"] == 3 else 1.0)
            return p, o, {"loss": loss}

        mgr = CheckpointManager(tmp_path)
        loop = TrainLoop(poisoned_step, params, opt_state,
                         iter(SyntheticBatcher(2, 16, cfg.vocab_size)), mgr,
                         TrainLoopConfig(total_steps=5, checkpoint_every=100,
                                         async_checkpoint=False))
        summary = loop.run()
        assert summary["skipped_nan"] == 1
        assert summary["final_step"] == 5


class TestStreamTraining:
    def test_stream_batcher_feeds_loop(self):
        from repro.streamsim import (Producer, StreamQueue, VirtualClock,
                                     make_stream, nsa, preprocess)
        cfg = tiny_lm()
        sim = nsa(preprocess(make_stream("traffic", scale=0.01, seed=3)), 60)
        q = StreamQueue(maxsize=64)
        threading.Thread(
            target=Producer(sim, q, clock=VirtualClock()).run,
            daemon=True).start()
        batcher = StreamBatcher(q, batch=2, seq=32, vocab=cfg.vocab_size)
        batches = list(batcher)
        assert len(batches) >= 3
        for b in batches[:3]:
            assert b["inputs"].shape == (2, 32)
            assert b["inputs"].min() >= 1
            assert b["inputs"].max() < cfg.vocab_size
            # labels are inputs shifted by one position
            np.testing.assert_array_equal(b["inputs"][:, 1:],
                                          b["labels"][:, :-1])


class TestCompression:
    def test_int8_compressed_dp_matches_fp32(self):
        if len(jax.devices()) < 2:
            pytest.skip("needs >=2 devices (run via subprocess test)")

    def test_quantize_roundtrip(self):
        from repro.distributed.compression import dequantize, quantize
        g = jnp.asarray(np.random.default_rng(0).normal(0, 2, (256,)),
                        jnp.float32)
        q, s = quantize(g)
        err = np.abs(np.asarray(dequantize(q, s) - g))
        assert err.max() <= float(s) * 0.5 + 1e-6

    def test_compressed_training_converges_subprocess(self, tmp_path):
        """Run a 2-device DP compressed-gradient training in a subprocess
        (needs its own XLA device-count flag)."""
        import subprocess
        import sys
        code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.paper_stream import consumer_lm
from repro.models import transformer as T
from repro.distributed.compression import make_compressed_dp_grad, ef_init
from repro.training.optimizer import AdamW, adamw_init, adamw_update
cfg = consumer_lm().replace(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                            head_dim=16, d_ff=128, vocab_size=512,
                            loss_chunk=16)
from repro.launch.mesh import auto_axes
mesh = jax.make_mesh((2,), ("data",), auto_axes(1))
params = T.init_params(cfg, jax.random.PRNGKey(0))
ef = ef_init(params)
opt = AdamW(lr=3e-3, warmup_steps=2, total_steps=40)
opt_state = adamw_init(params)
grad_fn = make_compressed_dp_grad(
    lambda p, b: T.loss_fn(cfg, p, b)[0], mesh, "data")
rng = np.random.default_rng(0)
chunk = rng.integers(1, 512, (4, 33), dtype=np.int32)
batch = {"inputs": jnp.asarray(chunk[:, :-1]),
         "labels": jnp.asarray(chunk[:, 1:])}
first = last = None
for i in range(30):
    loss, grads, ef = grad_fn(params, batch, ef)
    params, opt_state, _ = adamw_update(opt, grads, opt_state, params)
    if i == 0: first = float(loss)
    last = float(loss)
assert last < first * 0.7, (first, last)
print("OK", first, last)
"""
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=600,
                           env={**__import__("os").environ,
                                "PYTHONPATH": "src"},
                           cwd=__import__("pathlib").Path(
                               __file__).parent.parent)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "OK" in r.stdout
