"""Where the persistent compile cache goes: outside's choice, else the
checkout's fixed ``.jax_cache``."""
import os
import subprocess
import sys

import jax

from lighthouse_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_default_is_the_checkouts_fixed_directory(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.configure()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert os.environ[compile_cache.ENV] == path


def test_cache_placed_from_outside_takes_every_entry(tmp_path):
    code = ("from lighthouse_tpu.utils import compile_cache\n"
            "print(compile_cache.configure())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: jnp.cumsum(x * 3))(jnp.arange(977.0))"
            ".block_until_ready()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry written"
