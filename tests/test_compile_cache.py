"""Where the persistent compilation cache lives (``launch/compile_cache``):
the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, otherwise
``.jax_cache`` under the checkout — and compiled programs land there.  Each
case runs in a fresh interpreter so the test process's own JAX config is
never touched."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import pathlib, sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
where = compile_cache.enable(pathlib.Path(sys.argv[1]))
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(7.0)).block_until_ready()
print(where)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_dir_and_writes(tmp_path, from_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    root = tmp_path / "checkout"
    want = tmp_path / "from_env" if from_env else root / ".jax_cache"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(root)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir()), "nothing was written to the cache"
    if from_env:
        assert not root.exists(), "the checkout default was written as well"
