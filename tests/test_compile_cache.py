"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, and otherwise to <repo>/.jax_cache (a subprocess each, so the
setting never leaks into this test process)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CODE = """
import os, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
d = enable_compile_cache()
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):   # write an entry there
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((32, 32))).block_until_ready()
print("DIR", d, jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_location(from_env, tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run([sys.executable, "-c", CODE], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines() if l.startswith("DIR "))
    _, returned, configured = line.split()
    want = str(tmp_path) if from_env else os.path.join(REPO, ".jax_cache")
    assert returned == configured == want
    if from_env:       # entries land there, and nowhere else under tmp_path
        assert any(n.endswith("-cache") for n in os.listdir(tmp_path))
