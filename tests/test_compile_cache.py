"""The entry points' persistent compilation cache directory."""
import os
import subprocess
import sys

import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR

# turn the cache on, compile one program, and report where it went
_PROBE = ("import os, jax, jax.numpy as jnp; "
          "from repro.launch.compile_cache import enable_compile_cache as e; "
          "p = e(); jax.jit(lambda x: x * 3 - 1)(jnp.ones(5)).block_until_ready(); "
          "print(p, jax.config.jax_compilation_cache_dir, "
          "any(f.startswith('jit__lambda') for f in os.listdir(p)))")


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO_CACHE_DIR.parent / "src"), env.get("PYTHONPATH", "")])
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.split()


@pytest.mark.parametrize("from_env", [False, True], ids=["unset", "from_env"])
def test_cache_dir_is_env_or_fixed_checkout_path(from_env, tmp_path):
    env_dir = str(tmp_path / "cache") if from_env else None
    used, configured, written = _probe(env_dir)
    want = env_dir or str(REPO_CACHE_DIR)
    assert used == configured == want
    assert written == "True"
    assert REPO_CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
