"""JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` when
set, else one fixed directory in the checkout — the same in every process
(the path is part of the cache key, so a moving directory never hits)."""

import os
import subprocess
import sys

from parquet_tpu.utils.compile_cache import DEFAULT_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = ("import jax; from parquet_tpu.utils.compile_cache import "
         "setup_compile_cache as s; print(s()); "
         "print(jax.config.jax_compilation_cache_dir)")


def cache_dirs(**env):
    e = {k: v for k, v in os.environ.items()
         if k != "JAX_COMPILATION_CACHE_DIR"}
    e.update(env, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", PROBE], cwd=REPO, env=e,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.split()


def test_env_cache_dir_is_honoured(tmp_path):
    d = str(tmp_path / "cache")
    assert cache_dirs(JAX_COMPILATION_CACHE_DIR=d) == [d, d]


def test_default_cache_dir_is_fixed_across_processes():
    first, second = cache_dirs(), cache_dirs()
    assert first == second == [DEFAULT_DIR, DEFAULT_DIR]
    assert DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
