"""Entry-point plumbing: the persistent compile-cache helper, the serve
launcher's shared engine assembly, and chip_smoke.py's refusal to run
anywhere but on a TPU."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.launch import compile_cache as CC

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    assert CC.enable_compile_cache() == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == CC.DEFAULT_DIR


def test_compile_cache_env_var_stands(monkeypatch, cache_dir_config):
    """JAX reads the variable itself; the helper sets no other path."""
    monkeypatch.setenv(CC.ENV_VAR, "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert CC.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_is_git_ignored():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()


def test_serve_launcher_builds_paged_engine():
    """build_engine is the one assembly the launcher and chip_smoke share:
    seeded params/profiles, the continuous paged engine, requests drain."""
    from repro.launch.serve import build_engine
    from repro.serve.engine import Request

    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    eng = build_engine(cfg, profiles=3, slots=2, max_seq=64,
                       continuous=True, page_size=16)
    assert eng.continuous and eng.store.profile_ids() == [0, 1, 2]
    reqs = [Request(uid=i, prompt=np.arange(5 + i) % cfg.vocab_size,
                    profile_id=i % 3, max_new_tokens=4) for i in range(4)]
    eng.run_until_drained(reqs)
    assert all(len(r.generated) == 4 for r in reqs)
    assert eng.serve_stats()["step_traces"] == 1


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
