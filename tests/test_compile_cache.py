"""Where `launch/compile_cache.setup_compile_cache` puts JAX's persistent
compilation cache: the directory `JAX_COMPILATION_CACHE_DIR` names when
it is set, `<checkout>/.jax_cache` otherwise.

Each case compiles in a child process, so the test session's own JAX
never has the cache turned on; the child's checkout is a temporary
directory.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"

CHILD = """
import sys
from pathlib import Path

import jax
import jax.numpy as jnp

import repro.launch.compile_cache as cc

cc.CHECKOUT = Path(sys.argv[1])
print(cc.setup_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_cache_lands_where_configured(tmp_path, from_env):
    checkout = tmp_path / "checkout"
    env_dir = tmp_path / "env_cache"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(SRC)] + [p for p in [env.get("PYTHONPATH")] if p]
    )
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(checkout)],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    )
    want = env_dir if from_env else checkout / ".jax_cache"
    other = checkout / ".jax_cache" if from_env else env_dir
    assert out.stdout.split() == [str(want)]
    assert any(want.iterdir()), "nothing was cached"
    assert not other.exists()
