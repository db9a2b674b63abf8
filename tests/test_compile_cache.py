"""``repro.compile_cache``: where the entry points keep compiled programs.

Each case runs in a fresh interpreter, so the persistent cache it turns on
never reaches this test process.
"""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
PROBE = """
import jax, jax.numpy as jnp
from repro import compile_cache
print(compile_cache.enable())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


@pytest.mark.parametrize("case", ["env", "default"])
def test_cache_directory(case, tmp_path):
    from repro import compile_cache
    if case == "env":
        returned, configured = _run(tmp_path)
        assert returned == configured == str(tmp_path)
        assert any(tmp_path.iterdir()), "nothing was cached in the env dir"
    else:
        returned, configured = _run(None)
        assert returned == configured == str(compile_cache.REPO_CACHE_DIR)
        assert compile_cache.REPO_CACHE_DIR == REPO / ".jax_cache"
