"""Placement of JAX's persistent compile cache (utils/compile_cache.py).
Run in child processes: the test interpreter's own cache stays off."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROBE = ("import jax; "
         "from flink_tpu.utils.compile_cache import place_compile_cache; "
         "print(place_compile_cache()); "
         "print(jax.config.jax_compilation_cache_dir)")


def _probe(env_dir, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=120,
                         check=True).stdout.split()
    return out[-2:]


def test_env_var_wins_and_nothing_else_is_set(tmp_path):
    placed, jax_dir = _probe(str(tmp_path / "elsewhere"), str(tmp_path))
    assert placed == jax_dir == str(tmp_path / "elsewhere")


def test_default_is_a_fixed_path_in_the_checkout(tmp_path):
    # not the working directory, no pid, temp name or time in it
    placed, jax_dir = _probe(None, str(tmp_path))
    assert placed == jax_dir == os.path.join(REPO, ".jax_cache")
