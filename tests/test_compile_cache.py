"""Where the persistent compilation cache goes (repro.launch.compile_cache).

Each case runs in a child process so the parent's JAX configuration is
never touched.
"""

import json
import os
import subprocess
import sys

from repro.launch.compile_cache import ENV_VAR, REPO_CACHE_DIR

_PROBE = r"""
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
placed = enable_compile_cache()
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 3.0 + x)(jnp.ones(7)).block_until_ready()
print("CACHE:" + json.dumps(dict(
    placed=placed, config=jax.config.jax_compilation_cache_dir)))
"""


def _child(env_dir, compile_something):
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env.update(JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=os.pathsep.join(sys.path))
    if env_dir is not None:
        env[ENV_VAR] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile=compile_something)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("CACHE:"))
    return json.loads(line[len("CACHE:"):])


def _listing(path):
    if not os.path.isdir(path):
        return None
    return sorted(os.listdir(path))


def test_env_dir_is_the_only_cache(tmp_path):
    """With $JAX_COMPILATION_CACHE_DIR set, JAX writes there and the helper
    places no other directory."""
    before = _listing(REPO_CACHE_DIR)
    got = _child(tmp_path / "x", True)
    assert got["placed"] == got["config"] == str(tmp_path / "x")
    assert os.listdir(tmp_path / "x"), "nothing was cached under the env dir"
    assert _listing(REPO_CACHE_DIR) == before


def test_repo_dir_without_env():
    """Without the variable the cache goes to the fixed <repo>/.jax_cache —
    never a path built from a temporary name, a pid or the time."""
    got = _child(None, False)
    assert got["placed"] == got["config"] == REPO_CACHE_DIR
    assert REPO_CACHE_DIR == os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
