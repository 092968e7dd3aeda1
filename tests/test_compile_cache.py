"""Where ``repro.compile_cache.enable_compile_cache`` puts JAX's cache.

Each case runs in a fresh interpreter: turning the persistent cache on is
process-wide, and the test workers must keep it off.
"""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
CHILD = """
import jax
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    from repro.compile_cache import CHECKOUT_CACHE_DIR, ENV_VAR
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop(ENV_VAR, None)
    if from_env:
        env[ENV_VAR] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", CHILD], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    returned, configured = out.stdout.split()
    # the environment's directory wins and nothing in code overrides it;
    # otherwise one fixed directory inside the checkout
    want = env[ENV_VAR] if from_env else CHECKOUT_CACHE_DIR
    assert returned == configured == want
    if not from_env:
        assert os.path.dirname(want) == os.path.normpath(
            os.path.join(SRC, ".."))
