"""The entry points' persistent-compile-cache rule
(``repro.launch.compile_cache``): ``$JAX_COMPILATION_CACHE_DIR`` when set
and nothing else, otherwise the fixed ``<repo>/.jax_cache``; library
modules leave the setting alone when imported. Each case runs in a fresh
interpreter, because JAX reads the environment variable at import."""

import os
import subprocess
import sys
from pathlib import Path

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]

# compile one small program with every compile eligible for the cache
_COMPILE = """
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def _run(code: str, env_dir: str | None = None) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = env_dir
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_default_dir_is_fixed_under_repo():
    assert compile_cache.REPO_CACHE_DIR == REPO / ".jax_cache"


def test_library_imports_leave_cache_unset():
    got = _run("import jax\n"
               "import repro.serving.engine, repro.launch.serve, "
               "repro.kernels.ops, repro.models.api\n"
               "print(repr(jax.config.jax_compilation_cache_dir))")
    assert got == "None"


def test_env_dir_is_used_and_nothing_else_set(tmp_path):
    env_dir = tmp_path / "env_cache"
    got = _run("import jax\n"
               "from repro.launch.compile_cache import enable_compile_cache\n"
               "used = enable_compile_cache()\n" + _COMPILE
               + "print(used, jax.config.jax_compilation_cache_dir)",
               env_dir=str(env_dir))
    assert got.split() == [str(env_dir)] * 2
    assert any(env_dir.iterdir()), "no cache entry written"


def test_without_env_uses_repo_dir(tmp_path):
    # the rule's fallback, pointed at a scratch dir so the test leaves
    # the checkout's own cache alone
    fallback = tmp_path / "repo_cache"
    got = _run("import jax\n"
               "from pathlib import Path\n"
               "from repro.launch import compile_cache\n"
               f"compile_cache.REPO_CACHE_DIR = Path({str(fallback)!r})\n"
               "used = compile_cache.enable_compile_cache()\n" + _COMPILE
               + "print(used, jax.config.jax_compilation_cache_dir)")
    assert got.split() == [str(fallback)] * 2
    assert any(fallback.iterdir()), "no cache entry written"
