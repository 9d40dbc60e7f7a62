"""Where the entry points put jax's persistent compile cache
(``launch/compile_cache.py``).  Each case runs in a fresh interpreter so the
test process itself never turns the cache on."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache
got = enable_compile_cache()
print("RETURNED", got)
print("CONFIG", jax.config.jax_compilation_cache_dir)
print("CHECKOUT", CHECKOUT_CACHE_DIR)
if got is not None and {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(3.0)).block_until_ready()
"""


def _probe(env_extra: dict, *, compile_: bool = False) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_ENABLE_COMPILATION_CACHE")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC), **env_extra)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(PROBE.format(compile=compile_))],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr
    return dict(line.split(" ", 1) for line in out.stdout.splitlines())


def test_env_dir_is_used_and_nothing_else(tmp_path):
    got = _probe({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}, compile_=True)
    assert got["RETURNED"] == str(tmp_path)
    assert got["CONFIG"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "the compile did not land in the env dir"


def test_default_is_the_checkout_cache_dir():
    got = _probe({})
    assert got["RETURNED"] == got["CHECKOUT"] == got["CONFIG"]
    assert got["CHECKOUT"] == str(SRC.parent / ".jax_cache")


def test_disabled_cache_sets_nothing():
    got = _probe({"JAX_ENABLE_COMPILATION_CACHE": "false"})
    assert got["RETURNED"] == "None"
    assert got["CONFIG"] == "None"
