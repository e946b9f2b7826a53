"""The persistent compilation cache lands where `use_compile_cache` says:
in ``JAX_COMPILATION_CACHE_DIR`` when it is set, else in ``<repo>/.jax_cache``.

Each case compiles a fresh program in its own process (JAX reads the
variable when it starts) and looks for that program's entry."""

import os
import subprocess
import sys
import uuid
from pathlib import Path

import pytest

from repro.utils.compile_cache import ENV, REPO_CACHE_DIR

SRC = Path(__file__).resolve().parents[1] / "src"

SCRIPT = """
import sys
sys.path.insert(0, {src!r})
from repro.utils.compile_cache import use_compile_cache
print(use_compile_cache())
import jax
import jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
def {name}(x):
    return x * {nonce} + 1
print(int(jax.jit({name})(jnp.arange(4)).sum()))
"""


def _entries(d: Path, name: str):
    return sorted(p.name for p in d.glob(f"jit_{name}-*")) if d.is_dir() else []


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "repo"])
def test_cache_entries_land_in_one_place(env_set, tmp_path):
    nonce = uuid.uuid4().int % 1000003
    name = f"cache_probe_{nonce}"
    env = dict(os.environ)
    env.pop(ENV, None)
    if env_set:
        env[ENV] = str(tmp_path)
    expected = tmp_path if env_set else REPO_CACHE_DIR
    try:
        out = subprocess.run(
            [sys.executable, "-c",
             SCRIPT.format(src=str(SRC), name=name, nonce=nonce)],
            env=env, capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.splitlines()[0] == str(expected)
        assert _entries(expected, name), f"no cache entry in {expected}"
        if env_set:
            assert not _entries(REPO_CACHE_DIR, name)
    finally:
        for entry in _entries(REPO_CACHE_DIR, name):
            (REPO_CACHE_DIR / entry).unlink()
