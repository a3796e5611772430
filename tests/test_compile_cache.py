"""Where the entry points' persistent compilation cache lands."""
import os
import subprocess
import sys
import tempfile

import jax

from repro import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE_ONE = (
    "import jax\n"
    "from repro.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
    "jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready()\n"
)


def test_cache_lands_in_env_dir_when_set():
    with tempfile.TemporaryDirectory() as d:
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=d,
                   JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.join(REPO, "src"))
        out = subprocess.run([sys.executable, "-c", _COMPILE_ONE], env=env,
                             capture_output=True, text=True, timeout=120,
                             check=True)
        assert out.stdout.strip().splitlines()[-1] == d
        assert os.listdir(d), "nothing was written to the cache directory"


def test_cache_defaults_to_fixed_ignored_path_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
