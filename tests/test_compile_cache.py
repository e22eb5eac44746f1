"""Placement of the persistent compilation cache (repro.compile_cache):
``JAX_COMPILATION_CACHE_DIR`` wins when set, else one fixed directory
inside the checkout. Each case runs in a fresh CPU-only interpreter so
the cache setting never leaks into other tests."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import setup_compile_cache
print(setup_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile=compile_)],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()[:2]


def test_cache_entries_land_in_env_dir(tmp_path):
    cache = tmp_path / "jaxcache"
    returned, configured = _probe(cache, compile_=True)
    assert returned == configured == str(cache)
    assert any(cache.iterdir())


def test_cache_defaults_to_fixed_checkout_dir():
    returned, configured = _probe(None, compile_=False)
    assert returned == configured == os.path.join(ROOT, ".jax_cache")
