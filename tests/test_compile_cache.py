"""The persistent-compile-cache rule of `repro.compile_cache`: entries
land in $JAX_COMPILATION_CACHE_DIR when it is set, else in
<checkout>/.jax_cache, which git ignores. Each case runs in a fresh
process, since JAX fixes its cache directory at the first compile."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

_COMPILE = """
    import jax, jax.numpy as jnp
    import repro.compile_cache as cc
    {setup}
    print(cc.enable_compile_cache())
    jax.jit(lambda x: jnp.sort(x) * 2)(jnp.arange(64.0)).block_until_ready()
"""


def _run(setup: str, env_dir):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = textwrap.dedent(_COMPILE.format(setup=setup))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_entries_land_where_the_rule_says(tmp_path, from_env):
    env_dir = tmp_path / "from_env"
    checkout = tmp_path / "checkout"
    # the checkout is redirected so the test never writes into the repo
    used = _run(f"cc.CHECKOUT = {str(checkout)!r}",
                env_dir if from_env else None)
    want = env_dir if from_env else checkout / ".jax_cache"
    other = checkout / ".jax_cache" if from_env else env_dir
    assert used == str(want)
    assert want.is_dir() and any(want.iterdir())
    assert not other.exists()


def test_checkout_cache_dir_is_git_ignored():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
