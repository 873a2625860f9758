"""Where the persistent compile cache lands (``repro.compile_cache``).

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX configuration, and the test workers must keep theirs untouched.
"""

import os
import subprocess
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]


def _run(code: str, **env_over) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_over)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(CHECKOUT / "src"),
                                         env.get("PYTHONPATH", "")])
    return subprocess.run([sys.executable, "-c", code], env=env, check=True,
                          capture_output=True, text=True).stdout.strip()


def test_env_dir_receives_compiled_programs(tmp_path):
    cache = tmp_path / "jax-cache"
    out = _run(
        "import jax, jax.numpy as jnp\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()\n",
        JAX_COMPILATION_CACHE_DIR=str(cache))
    assert out == str(cache)
    assert any(cache.iterdir())


def test_default_dir_is_fixed_in_the_checkout():
    out = _run("from repro.compile_cache import enable_compile_cache\n"
               "print(enable_compile_cache())\n")
    assert out == str(CHECKOUT / ".jax_cache")


def test_library_imports_leave_the_cache_alone():
    out = _run("import jax\n"
               "import repro.api, repro.core, repro.kernels.ops\n"
               "print(jax.config.jax_compilation_cache_dir)\n")
    assert out == "None"
