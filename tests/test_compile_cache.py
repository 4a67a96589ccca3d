"""The entry points' compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise to ``.jax_cache/`` in the checkout, whatever the
working directory.  Each case runs in a fresh interpreter: the cache is
process-wide JAX configuration."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(tmp_path, from_env):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
        # cache even a tiny program, to see where entries land
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        if jax.config.jax_persistent_cache_min_compile_time_secs == 0:
            jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    where = out.stdout.strip().splitlines()[-1]
    if from_env:
        assert where == str(tmp_path / "cache")
        assert os.listdir(where), "no cache entry written"
    else:
        assert where == os.path.join(REPO, ".jax_cache")
    assert not (tmp_path / ".jax_cache").exists()
