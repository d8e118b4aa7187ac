"""Where ``launch/compile_cache.enable`` puts JAX's persistent cache:
``JAX_COMPILATION_CACHE_DIR`` when it is set (and nothing set in code),
otherwise the fixed ``<repo>/.jax_cache``. Each case runs in a fresh
process, as an entry point does, and compiles one function slowly
enough to be written."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

PROBE = """
import jax, jax.numpy as jnp
from repro.launch import compile_cache
print("dir", compile_cache.enable())
print("config", jax.config.jax_compilation_cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(7.0)).block_until_ready()
"""


def _run(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return dict(line.split(" ", 1) for line in out.stdout.splitlines())


def test_cache_dir_from_environment(tmp_path):
    cache = tmp_path / "cache"
    got = _run(cache)
    assert got == {"dir": str(cache), "config": str(cache)}
    assert any(cache.iterdir()), "no cache entry written"


def test_cache_dir_defaults_to_checkout():
    from repro.launch import compile_cache

    default = ROOT / ".jax_cache"
    assert compile_cache.DEFAULT_DIR == default
    got = _run(None)
    assert got == {"dir": str(default), "config": str(default)}
    assert any(default.iterdir()), "no cache entry written"
