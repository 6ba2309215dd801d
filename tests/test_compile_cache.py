"""Persistent compile cache placement (``repro.launch.compile_cache``).

Each case runs in a fresh process: JAX settles on a cache directory at
its first compile, so the placement must be observed from process start.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent(
    """
    import sys
    import jax, jax.numpy as jnp
    import repro.launch.compile_cache as cc

    cc.CHECKOUT_ROOT = sys.argv[1]  # a stand-in checkout
    print(cc.place_compile_cache())
    # cache even this tiny program (the default skips compiles under 1 s)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
    """
)


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
def test_cache_lands_in_one_fixed_place(tmp_path, env_set):
    checkout = tmp_path / "checkout"
    env_dir = tmp_path / "env_cache"
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(checkout)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    want = env_dir if env_set else checkout / ".jax_cache"
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir()), "no cache entry written"
    other = checkout / ".jax_cache" if env_set else env_dir
    assert not other.exists()
