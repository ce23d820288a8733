"""Where the persistent compilation cache lands: ``JAX_COMPILATION_CACHE_DIR``
when it is set, otherwise the fixed ``.jax_cache/`` in the checkout."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch.compile_cache import ENV_VAR, compile_cache_dir

_CHILD = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    print(enable_compile_cache(sys.argv[1]))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
""")


def test_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "elsewhere"))
    assert compile_cache_dir(tmp_path) == str(tmp_path / "elsewhere")
    monkeypatch.delenv(ENV_VAR)
    assert compile_cache_dir(tmp_path) == str(tmp_path.resolve()
                                              / ".jax_cache")


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compiles_land_in_the_cache_dir(tmp_path, from_env):
    checkout = tmp_path / "checkout"
    checkout.mkdir()
    env = {k: v for k, v in os.environ.items() if k != ENV_VAR}
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    env["JAX_PLATFORMS"] = "cpu"
    want = checkout / ".jax_cache"
    if from_env:
        want = tmp_path / "from_env"
        env[ENV_VAR] = str(want)
    out = subprocess.run([sys.executable, "-c", _CHILD, str(checkout)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir())
    others = [p for p in checkout.iterdir() if p != want]
    assert others == []
