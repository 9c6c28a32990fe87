"""End-to-end launcher test: the production code path trains a tiny LM on
CPU and the averaged model's loss goes down."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.core.mllsgd import MLLConfig
from repro.launch.train import TrainLoopConfig, run_training


def test_run_training_loss_decreases():
    cfg = get_smoke_config("qwen2-0.5b")
    mll = MLLConfig(tau=2, q=2, eta=0.05, hub_topology="ring",
                    worker_rates=(1.0, 0.8, 1.0, 0.6))
    loop = TrainLoopConfig(steps=24, eval_every=8, seq_len=32,
                           batch_per_worker=4, tokens_per_worker=4096)
    out = run_training(cfg, mll, loop, num_subnets=2, workers_per_subnet=2,
                       log=lambda *a, **k: None)
    hist = out["history"]
    assert len(hist["avg_loss"]) >= 2
    assert np.isfinite(hist["avg_loss"]).all()
    assert hist["avg_loss"][-1] < hist["avg_loss"][0]


def test_run_training_checkpoint(tmp_path):
    cfg = get_smoke_config("xlstm-125m")
    mll = MLLConfig(tau=2, q=1, eta=0.05)
    loop = TrainLoopConfig(steps=4, eval_every=4, seq_len=16,
                           batch_per_worker=2, tokens_per_worker=2048,
                           checkpoint_dir=str(tmp_path / "ck"),
                           checkpoint_every=2)
    out = run_training(cfg, mll, loop, num_subnets=1, workers_per_subnet=2,
                       log=lambda *a, **k: None)
    from repro.train import checkpoint
    u, step = checkpoint.restore(str(tmp_path / "ck"), out["avg_params"])
    assert step == 4
    for a, b in zip(jax.tree.leaves(out["avg_params"]), jax.tree.leaves(u)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("placed", [None, "elsewhere"])
def test_enable_compile_cache(monkeypatch, tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to the fixed, gitignored <repo>/.jax_cache."""
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    if placed is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        got = compile_cache.enable_compile_cache()
        after = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if placed is None:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == after == os.path.join(repo, ".jax_cache")
    else:
        assert got == str(tmp_path) and after == before
