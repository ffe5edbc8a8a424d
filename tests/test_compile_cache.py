"""The persistent compilation cache is placed from outside the program:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` at the repo
root.  Each case runs in a fresh interpreter so this process's JAX config
stays untouched."""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
path = enable_compile_cache()
print(path)
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


def test_env_var_directory_receives_entries(tmp_path):
    cache = tmp_path / "cache"
    path, configured = _probe(cache, True)
    assert path == configured == str(cache)
    assert any(cache.iterdir()), "no cache entry was written"


def test_default_is_fixed_repo_directory():
    path, configured = _probe(None, False)
    assert path == configured == str(REPO / ".jax_cache")


def test_nothing_is_set_at_import():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = ("import jax, repro.compile_cache, repro.service.server, "
            "repro.service.asyncserver\n"
            "print(jax.config.jax_compilation_cache_dir)")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["None"]
