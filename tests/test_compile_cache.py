"""The persistent compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, or else at the one fixed directory inside the checkout."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import jax, jax.numpy as jnp
from repro.launch import compile_cache
print("DIR", compile_cache.enable())
print("CONFIG", jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
"""


def _probe(env_dir, compile_: bool):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c",
                          PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = dict(ln.split(" ", 1) for ln in out.stdout.splitlines()
                 if ln.startswith(("DIR ", "CONFIG ")))
    return lines["DIR"], lines["CONFIG"]


def test_env_dir_is_used_and_filled(tmp_path):
    cache = str(tmp_path / "cache")
    d, cfg = _probe(cache, compile_=True)
    assert d == cfg == cache
    assert os.listdir(cache), "no cache entry written to the env directory"


def test_default_is_fixed_dir_in_checkout():
    from repro.launch import compile_cache

    d, cfg = _probe("", compile_=False)
    assert d == cfg == compile_cache.DEFAULT_DIR
    assert d == os.path.join(ROOT, ".jax_cache")
