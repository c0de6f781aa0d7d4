"""Where the persistent compilation cache lands (repro.compile_cache):
in JAX_COMPILATION_CACHE_DIR when set, else at a fixed directory inside
the checkout, and nowhere at all on a bare ``import repro``.  Each case
runs in a fresh interpreter, because JAX's cache is process state."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")

COMPILE = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro import compile_cache
    path = compile_cache.enable(sys.argv[1])
    # cache even a tiny program, so one compile shows where it lands
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) * 3).lower(jnp.ones(5)).compile()
    print(path)
""")


def _run(code, *args, cache_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    proc = subprocess.run([sys.executable, "-c", code, *map(str, args)],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return proc.stdout.strip().splitlines()[-1]


def _entries(d: Path):
    return list(d.iterdir()) if d.is_dir() else []


def test_unset_lands_in_the_checkout(tmp_path):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    path = _run(COMPILE, checkout)
    assert Path(path) == checkout.resolve() / ".jax_cache"
    assert _entries(Path(path))


@pytest.mark.parametrize("runs", [1, 2])
def test_env_dir_is_used_and_nothing_else(tmp_path, runs):
    checkout, env_dir = tmp_path / "checkout", tmp_path / "env_cache"
    checkout.mkdir()
    for _ in range(runs):
        path = _run(COMPILE, checkout, cache_env=env_dir)
    assert Path(path) == env_dir
    assert _entries(env_dir)
    assert not (checkout / ".jax_cache").exists()


def test_import_turns_nothing_on():
    code = ("import jax, repro.api, repro.diffusion.executor, "
            "repro.compile_cache; "
            "print(jax.config.jax_compilation_cache_dir)")
    assert _run(code) == "None"
