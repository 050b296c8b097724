"""The launchers' entry points, called in-process at smoke size on the CPU,
and the compile-cache placement they share."""

import math
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch import compile_cache
from repro.launch.serve import serve
from repro.launch.train import train
from repro.models import get_config, get_smoke_config, init_model, init_params

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("smollm-360m")


def test_train_switches_to_degraded_step(cfg):
    res = train(cfg, steps=4, seq_len=16, batch=4, sync="r2ccl",
                comm_mode="ring", fail_at_step=2, log=lambda _: None)
    assert np.isfinite(res.losses).all()
    assert abs(res.losses[0] - math.log(cfg.vocab_size)) < 1.0
    assert res.schedules == ["healthy", "healthy", "degraded", "degraded"]
    assert set(res.compile_s) == {"healthy", "degraded"}
    assert len(res.step_s) == 4 and int(res.state.step) == 4


def test_train_failure_at_step_zero_builds_only_degraded(cfg):
    res = train(cfg, steps=2, seq_len=16, batch=4, sync="r2ccl",
                fail_at_step=0, log=lambda _: None)
    assert set(res.compile_s) == {"degraded"}
    assert res.schedules == ["degraded", "degraded"]


def test_train_xla_sync_cannot_adapt(cfg):
    lines = []
    res = train(cfg, steps=2, seq_len=16, batch=4, sync="xla",
                fail_at_step=1, log=lines.append)
    assert res.schedules == ["healthy", "healthy"]
    assert any("xla sync cannot adapt" in s for s in lines)


def test_serve_recovers_once(cfg):
    out = serve(cfg, requests=3, prompt_len=8, max_new=5, strategy="r2ccl",
                fail_at_step=2)
    assert len(out.results) == 3
    for r in out.results:
        assert len(r.tokens) == 5
        assert all(0 <= t < cfg.vocab_size for t in r.tokens)
        assert r.failovers == 1
    assert len(out.engine.control_plane.ledger.entries) == 1
    assert out.warmup_s > 0


def test_serve_failure_keeps_tokens(cfg):
    """Hot repair is lossless: the failed batch decodes the same tokens
    as a healthy one."""
    healthy = serve(cfg, requests=2, prompt_len=8, max_new=5)
    failed = serve(cfg, requests=2, prompt_len=8, max_new=5, fail_at_step=1)
    assert [r.tokens for r in healthy.results] == [r.tokens for r in failed.results]
    assert healthy.engine.control_plane.ledger.entries == []


def test_serve_rejects_encoder_only():
    with pytest.raises(ValueError, match="encoder-only"):
        serve(get_config("hubert-xlarge"))


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_compile_cache_dir_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = Path(compile_cache.compile_cache_dir())
    assert path == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_init_params_matches_init_model(cfg):
    key = jax.random.PRNGKey(3)
    eager, _ = init_model(key, cfg)
    jitted = init_params(key, cfg)
    assert jax.tree_util.tree_structure(eager) == jax.tree_util.tree_structure(jitted)
    for x, y in zip(jax.tree_util.tree_leaves(eager), jax.tree_util.tree_leaves(jitted)):
        assert x.shape == y.shape and x.dtype == y.dtype
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), rtol=1e-6, atol=1e-6)
