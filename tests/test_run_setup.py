"""What a process sets up before it serves: weights made from a seed are the
same in every process, and the persistent compilation cache lands where the
environment says, or else in one fixed directory of the checkout.

Each case runs in a fresh CPU interpreter, so nothing here changes the test
process's own JAX configuration."""
import os
import subprocess
import sys

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PARAM_DIGEST = """
import hashlib
import jax
import numpy as np
from repro.configs import get_arch
from repro.models import transformer as T
from repro.models.params import init_params
params = init_params(T.param_defs(get_arch("olmo-1b").reduced()),
                     jax.random.PRNGKey(0))
h = hashlib.sha256()
for leaf in jax.tree.leaves(params):
    h.update(np.asarray(leaf, np.float32).tobytes())
print(h.hexdigest())
"""

CACHE_DIR = """
import jax
import jax.numpy as jnp
from repro.launch.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()
"""


def _python(code, **env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
                **env)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=base, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.split()


def test_init_params_same_in_every_process():
    """Per-leaf keys come from the parameter path; ``hash()`` of a str is
    salted per process, so it must not be what derives them."""
    a = _python(PARAM_DIGEST, PYTHONHASHSEED="1")
    b = _python(PARAM_DIGEST, PYTHONHASHSEED="2")
    assert a == b


def test_compile_cache_follows_environment(tmp_path):
    cache = str(tmp_path / "jax_cache")
    used, configured = _python(
        CACHE_DIR, JAX_COMPILATION_CACHE_DIR=cache,
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert used == configured == cache
    assert os.listdir(cache), "no compiled program was cached"


def test_compile_cache_defaults_to_checkout():
    """Without the variable the cache is one fixed directory inside the
    checkout (listed in .gitignore). Compile-time threshold left at its
    default, so this quick program is not written there."""
    used, configured = _python(CACHE_DIR)
    assert used == configured == CHECKOUT_CACHE_DIR
    assert CHECKOUT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
