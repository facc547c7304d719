"""The persistent compilation cache lands in its one fixed directory:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.

Each case runs in a fresh interpreter: JAX reads the variable once, at
import, and the cache directory cannot be moved after first use."""
import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

_PROG = """
import sys
import jax, jax.numpy as jnp
from repro.launch.cache import use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(use_compile_cache(sys.argv[1]))
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _run(checkout: Path, cache_env=None) -> str:
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    out = subprocess.run([sys.executable, "-c", _PROG, str(checkout)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_cache_defaults_to_checkout(tmp_path):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    used = _run(checkout)
    assert used == str(checkout / ".jax_cache")
    assert any((checkout / ".jax_cache").iterdir())


def test_cache_follows_environment(tmp_path):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    cache = tmp_path / "elsewhere"
    used = _run(checkout, cache_env=cache)
    assert used == str(cache)
    assert any(cache.iterdir())
    assert not (checkout / ".jax_cache").exists()
