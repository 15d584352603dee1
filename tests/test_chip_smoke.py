"""chip_smoke.py's contract, as far as a machine without a chip can hold
it: the rehearsal runs every one-chip phase at tiny sizes in a child
forced to the CPU and says so in its last line; without the rehearsal
option a platform that is not a TPU is refused.  Plus the compile-cache
helper the entry scripts share."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from deepspeed_tpu.platform import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _smoke(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # one CPU device, like one chip
    return subprocess.run([sys.executable, "chip_smoke.py", *args],
                          cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)


def test_rehearsal_runs_every_phase_on_cpu_and_says_cpu():
    r = _smoke("--rehearse")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 1}}
    for name in ("block_until_ready", "profiler window", "kernels vs XLA",
                 "train", "serve", "serve-int8"):
        assert f"[{name}]" in r.stdout
    assert "FAILED" not in r.stdout


def test_refuses_a_platform_that_is_not_a_tpu():
    r = _smoke()
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


@pytest.fixture
def cache_config():
    """Leave jax's compile-cache settings as the suite found them (off)."""
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])


def test_suite_runs_without_a_compile_cache():
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_one_fixed_ignored_dir(monkeypatch,
                                                        cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.enable_compile_cache() == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == str(
        REPO / ".jax_compile_cache")
    assert ".jax_compile_cache/" in (REPO / ".gitignore").read_text().split()


def test_compile_cache_env_var_is_left_to_jax(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before
