"""Where JAX's persistent compilation cache lives: the directory named by
``$JAX_COMPILATION_CACHE_DIR`` when it is set, else ``<repo>/.jax_cache``.
Each case runs in a fresh interpreter, since JAX reads the variable when it
is imported."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(body: str, **env) -> str:
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        env=dict(base, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
                 **env),
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip()


def test_cache_goes_where_the_environment_says(tmp_path):
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.runtime import enable_compile_cache
        print(enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out == str(tmp_path)
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())


def test_cache_defaults_to_the_repo():
    out = _run("""
        import jax
        from repro.runtime import enable_compile_cache
        print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
    """)
    want = str(REPO / ".jax_cache")
    assert out == f"{want} {want}"
