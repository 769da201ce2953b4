"""``use_compile_cache``: JAX's persistent compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to the checkout's fixed
``.jax_cache``, and never to both.

Each case compiles in a fresh interpreter: JAX fixes the cache directory
at its first compile, so a test process that has compiled cannot be
pointed elsewhere."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

from repro.utils import env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# point the default at ``argv[1]`` (never the real checkout's cache),
# turn the cache on, and compile one program
CHILD = """
import sys
import jax
import jax.numpy as jnp
from repro.utils import env
env.DEFAULT_COMPILE_CACHE = sys.argv[1]
print(env.use_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def test_default_is_fixed_inside_the_checkout():
    assert env.DEFAULT_COMPILE_CACHE == os.path.join(REPO, ".jax_cache")


def _files(d) -> list[str]:
    return [f for _, _, fs in os.walk(d) for f in fs]


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env_var", "default"])
def test_cache_files_land_in_one_place(tmp_path, from_env):
    named, default = tmp_path / "named", tmp_path / "default"
    child_env = {k: v for k, v in os.environ.items()
                 if k != "JAX_COMPILATION_CACHE_DIR"}
    child_env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.path.join(REPO, "src"),
        # cache even this tiny, fast compile
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if from_env:
        child_env["JAX_COMPILATION_CACHE_DIR"] = str(named)
    proc = subprocess.run([sys.executable, "-c", CHILD, str(default)],
                          env=child_env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    want, other = (named, default) if from_env else (default, named)
    assert proc.stdout.split() == [str(want)]
    assert _files(want)
    assert not os.path.exists(other)
