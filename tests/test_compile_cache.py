"""Where the entry points keep JAX's persistent compilation cache
(``repro.launch.compile_cache``).  Each case runs in a fresh interpreter:
the cache settings are process-global JAX config."""
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import sys
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
print("DIR", enable_compile_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("DEFAULT", DEFAULT_DIR)
if len(sys.argv) > 1:  # write even this fast compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
"""


def _run(env_dir, compile_):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT] + (["compile"] if compile_ else []),
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert r.returncode == 0, r.stderr
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_cache_dir_from_environment_is_left_to_jax(tmp_path):
    cache = tmp_path / "jax_cache"
    out = _run(cache, compile_=True)
    assert out["DIR"] == out["CONFIG"] == str(cache)
    assert any(cache.iterdir()), "no entry written where the env says"
    assert out["DEFAULT"] != str(cache)


def test_cache_dir_defaults_to_fixed_checkout_path():
    out = _run(None, compile_=False)
    assert out["DIR"] == out["CONFIG"] == out["DEFAULT"]
    assert out["DEFAULT"] == os.path.join(
        os.path.realpath(ROOT), ".jax_cache")
