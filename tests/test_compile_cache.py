"""The persistent compilation cache goes where `repro.compile_cache` says."""
import os
import pathlib
import subprocess
import sys

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

# A child process, so the cache setting never leaks into this test worker.
_CHILD = """
import jax, jax.numpy as jnp
from repro import compile_cache
print(compile_cache.enable())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(17.0)).block_until_ready()
"""


def _run(env_dir):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop(compile_cache.ENV_VAR, None)
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    return out.stdout.strip().splitlines()[-1]


def test_env_var_places_the_cache(tmp_path):
    where = tmp_path / "cache"
    assert _run(where) == str(where)
    assert any(where.iterdir()), "no compiled program landed in the cache"


def test_default_is_one_fixed_ignored_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
