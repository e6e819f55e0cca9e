"""Launcher plumbing: the serve entry point starts on the devices that
exist, fails loudly when a request FAILS, and the compile cache lives in
one fixed directory."""
import os
import subprocess
import sys

import jax
import numpy as np

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE = [sys.executable, "-m", "repro.launch.serve", "--arch",
         "qwen1.5-0.5b", "--reduced", "--requests", "2", "--batch", "2",
         "--prompt-len", "8", "--gen-len", "3"]


def _serve(*extra, cache_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.pop("XLA_FLAGS", None)            # one host device
    return subprocess.run(SERVE + list(extra), capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=600)


def test_serve_without_mesh_starts_on_one_device(tmp_path):
    out = _serve(cache_dir=str(tmp_path / "cache"))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "req0:" in out.stdout and "req1:" in out.stdout
    assert "decode compiled 1x" in out.stdout


def test_serve_exits_nonzero_when_a_request_fails(tmp_path):
    """A checkpoint whose weights are NaN poisons every logit row: the
    engine quarantines each request as FAILED, and the launcher must
    exit non-zero instead of printing a green summary."""
    from repro.configs import get_config, reduce_config
    from repro.models.transformer import init_params
    from repro.train import checkpoint as ckpt

    cfg = reduce_config(get_config("qwen1.5-0.5b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    nan = jax.tree_util.tree_map(
        lambda x: np.full(x.shape, np.nan, x.dtype), params)
    ckpt.save_checkpoint(str(tmp_path / "ckpt"), 1, nan)
    out = _serve("--ckpt-dir", str(tmp_path / "ckpt"),
                 cache_dir=str(tmp_path / "cache"))
    assert out.returncode != 0, out.stdout[-3000:]
    assert "FAILED" in out.stderr
    assert "failed (non-finite logits" in out.stdout


def test_compile_cache_dir_honours_env_then_fixed_repo_path(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV, "/some/cache")
    assert compile_cache.compile_cache_dir() == "/some/cache"
    monkeypatch.delenv(compile_cache.ENV)
    assert compile_cache.compile_cache_dir() == os.path.join(REPO,
                                                             ".jax_cache")
    monkeypatch.setenv(compile_cache.ENV, "")
    assert compile_cache.compile_cache_dir() == os.path.join(REPO,
                                                             ".jax_cache")
