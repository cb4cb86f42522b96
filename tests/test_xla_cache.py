"""Where the entry points put JAX's persistent compilation cache.

Each case runs ``use_persistent_cache()`` in a fresh interpreter (the
test process itself never turns the cache on): with
``JAX_COMPILATION_CACHE_DIR`` set the cache is written there and the code
sets no directory; without it the directory is the fixed
``<checkout>/.jax_cache``.
"""
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PROBE = """
import json, os
import jax, jax.numpy as jnp
from repro.runtime.xla_cache import DEFAULT_DIR, use_persistent_cache
path = use_persistent_cache()
if os.environ.get("COMPILE"):
    jax.jit(lambda a: jnp.tanh(a @ a.T))(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"path": path, "config": jax.config.jax_compilation_cache_dir,
                  "default": str(DEFAULT_DIR)}))
"""


def _probe(env_extra, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra, PYTHONPATH=os.pathsep.join(
        [SRC, env.get("PYTHONPATH", "")]), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_written(tmp_path):
    cache = tmp_path / "cache"
    rec = _probe({"JAX_COMPILATION_CACHE_DIR": str(cache), "COMPILE": "1"})
    assert rec["path"] == rec["config"] == str(cache)
    assert any(cache.iterdir())            # the compile landed there


def test_default_dir_is_fixed_under_checkout():
    rec = _probe({}, drop=("JAX_COMPILATION_CACHE_DIR", "COMPILE"))
    root = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
    assert rec["path"] == rec["config"] == rec["default"]
    assert rec["default"] == os.path.join(root, ".jax_cache")
