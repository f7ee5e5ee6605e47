"""The persistent compile cache can be placed (multiverso_tpu/__init__.py):
``JAX_COMPILATION_CACHE_DIR`` set -> jax uses it and the package sets
nothing; unset -> ``<checkout>/.jax_cache``, a fixed path. Tier-1 itself
runs with the cache off (conftest) and leaves nothing in the checkout, so
each case runs in a child with its own environment."""

import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import sys
import jax, jax.numpy as jnp
import multiverso_tpu as mv
print("CACHE_DIR", jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    mv.init([])
    t = mv.create_table(mv.ArrayTableOption(64, name="c"))
    t.add(jnp.ones(64))
    assert float(t.get().sum()) == 64.0
    mv.shutdown()
"""


def _run(mode, **env_over):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    for k in ("JAX_COMPILATION_CACHE_DIR", "JAX_ENABLE_COMPILATION_CACHE"):
        env.pop(k, None)
    env.update(env_over)
    proc = subprocess.run([sys.executable, "-c", _CHILD, mode], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return next(ln.split(" ", 1)[1] for ln in proc.stdout.splitlines()
                if ln.startswith("CACHE_DIR"))


def test_cache_goes_where_the_environment_says(tmp_path):
    placed = str(tmp_path / "x")
    checkout_cache = os.path.join(_REPO, ".jax_cache")
    before = os.path.exists(checkout_cache)
    got = _run("compile", JAX_COMPILATION_CACHE_DIR=placed,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1")
    assert got == placed
    assert os.listdir(placed), "nothing was cached under the placed dir"
    assert os.path.exists(checkout_cache) == before   # and nowhere else


def test_default_cache_is_a_fixed_path_in_the_checkout():
    # Cache writes off: only where it WOULD go is checked here.
    got = _run("report", JAX_ENABLE_COMPILATION_CACHE="false")
    assert got == os.path.join(_REPO, ".jax_cache")
