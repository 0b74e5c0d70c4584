"""End-to-end behaviour tests: training convergence, serve loop, and the
Parm auto-schedule integration in a full model."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import subprocess_env
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.parallel.mesh import ParallelDims, make_mesh
from repro.train import Trainer, make_serve_step


def _mesh_dims(cfg):
    mesh = make_mesh((1, 1), ("data", "model"))
    dims = (ParallelDims(ep=("data",), esp=("model",), mp=("model",))
            if cfg.moe is not None
            else ParallelDims(dp=("data",), mp=("model",)))
    return mesh, dims


class TestTrainingIntegration:
    def test_loss_decreases_moe(self):
        """~120 steps on the synthetic bigram corpus must reduce CE."""
        cfg = get_config("gpt2-moe").reduced()
        mesh, dims = _mesh_dims(cfg)
        model = build_model(cfg)
        tr = Trainer(model, mesh, dims,
                     AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=150),
                     schedule="auto")
        params, opt = tr.setup(jax.random.PRNGKey(0))
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                      global_batch=8, n_heavy=4,
                                      heavy_prob=0.9))
        params, opt, hist = tr.run(params, opt, data, 150, log_every=30)
        assert hist[-1]["ce"] < hist[0]["ce"] - 0.25, hist
        assert all(np.isfinite(h["loss"]) for h in hist)

    def test_loss_decreases_dense(self):
        cfg = get_config("qwen1.5-0.5b").reduced()
        mesh, dims = _mesh_dims(cfg)
        model = build_model(cfg)
        tr = Trainer(model, mesh, dims,
                     AdamWConfig(lr=2e-3, warmup_steps=10, total_steps=100))
        params, opt = tr.setup(jax.random.PRNGKey(0))
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                                      global_batch=8, n_heavy=4,
                                      heavy_prob=0.9))
        params, opt, hist = tr.run(params, opt, data, 100, log_every=20)
        assert hist[-1]["ce"] < hist[0]["ce"] - 0.3, hist


class TestServeLoop:
    @pytest.mark.parametrize("name", ["qwen1.5-0.5b", "xlstm-350m",
                                      "qwen3-moe-30b-a3b"])
    def test_greedy_decode_runs(self, name):
        cfg = get_config(name).reduced()
        mesh, dims = _mesh_dims(cfg)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, T = 2, 12
        cache = model.init_cache(B, T)
        serve = jax.jit(make_serve_step(model, mesh, dims))
        tok = jnp.zeros((B, 1), jnp.int32)
        for t in range(T - 1):
            tok, cache = serve(params, cache,
                               {"tokens": tok, "step": jnp.int32(t)})
            assert tok.shape == (B, 1)
            assert int(tok.max()) < cfg.vocab_size

    def test_decode_matches_prefill_dense(self):
        """Greedy decode over a teacher-forced prompt must match the
        full-sequence forward logits (KV-cache correctness)."""
        self._decode_matches_forward(get_config("mistral-nemo-12b").reduced())

    def test_decode_matches_prefill_qk_norm(self):
        """The same with RMSNorm over each head's q and k (Qwen3): the
        decode path normalises the cached k as the forward does."""
        from dataclasses import replace
        cfg = replace(get_config("mistral-nemo-12b").reduced(), qk_norm=True)
        self._decode_matches_forward(cfg)

    @staticmethod
    def _decode_matches_forward(cfg):
        mesh, dims = _mesh_dims(cfg)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, L = 2, 16
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                                  cfg.vocab_size)
        logits, _ = jax.jit(lambda p, b: model.forward(
            p, b, mesh=mesh, dims=dims))(params, {"tokens": toks})
        cache = model.init_cache(B, L)
        errs = []
        step_fn = jax.jit(lambda p, c, b: model.decode_step(
            p, c, b, mesh=mesh, dims=dims))
        for t in range(L):
            lg, cache = step_fn(params, cache,
                                {"tokens": toks[:, t:t + 1],
                                 "step": jnp.int32(t)})
            errs.append(float(jnp.max(jnp.abs(lg[:, 0] - logits[:, t]))))
        assert max(errs) < 1e-3, errs


class TestMultiDeviceTraining:
    def test_sharded_training_runs(self, helpers_dir):
        r = subprocess.run(
            [sys.executable, os.path.join(helpers_dir,
                                          "run_sharded_train.py")],
            env=subprocess_env(8), capture_output=True, text=True,
            timeout=900)
        assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
        assert "SHARDED TRAIN OK" in r.stdout


ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


class TestChipSmoke:
    def test_compile_cache_dir(self, monkeypatch):
        from repro.launch import compile_cache
        was = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        try:
            got = compile_cache.enable_compile_cache()
            assert got == os.path.join(os.path.abspath(ROOT), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", was)

    def test_refuses_cpu(self):
        env = subprocess_env(1)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode != 0
        assert "no TPU" in r.stderr
        assert '"ok"' not in r.stdout

    def test_four_chip_phase_on_cpu_devices(self, helpers_dir):
        r = subprocess.run(
            [sys.executable, os.path.join(helpers_dir,
                                          "run_chip_smoke_four.py")],
            env=subprocess_env(4), capture_output=True, text=True,
            timeout=900)
        assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
        assert "FOUR CHIP PHASE OK" in r.stdout
