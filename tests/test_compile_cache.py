"""The persistent compile cache goes where the entry points put it.

Each case runs in a fresh interpreter: JAX initialises its cache once per
process.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE = r"""
import sys
import jax, jax.numpy as jnp
from repro import compile_cache
import repro.core, repro.serving  # importing the library sets no cache
assert jax.config.jax_compilation_cache_dir == (
    sys.argv[2] if sys.argv[2] != "-" else None)
compile_cache.REPO_ROOT = sys.argv[1]
print(compile_cache.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
"""


def _run(root, env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(REPO, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _COMPILE, root, env_dir or "-"], env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip()


def _files(d):
    return [f for _, _, fs in os.walk(d) for f in fs] if os.path.isdir(d) \
        else []


def test_cache_defaults_to_repo_root(tmp_path):
    root = str(tmp_path / "checkout")
    path = _run(root, None)
    assert path == os.path.join(root, ".jax_cache")
    assert _files(path)


def test_cache_env_var_wins(tmp_path):
    root, env_dir = str(tmp_path / "checkout"), str(tmp_path / "elsewhere")
    assert _run(root, env_dir) == env_dir
    assert _files(env_dir)
    assert not _files(os.path.join(root, ".jax_cache"))


def test_cache_dir_is_ignored_by_git():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()



_KEY = r"""
import jax, jax.numpy as jnp
from jax._src import cache_key
from repro import compile_cache
compile_cache.enable_compile_cache()

def scoped(name):
    def f(x):
        with jax.named_scope(name):
            return jnp.sin(x) * 3.0
    return f

def key(f):
    lowered = jax.jit(f).lower(jnp.arange(7.0))
    h = cache_key.hashlib.sha256()
    cache_key._hash_computation(h, lowered._lowering.stablehlo(),
                                cache_key.IgnoreCallbacks.NO)
    return h.hexdigest()

def first_caller(name): return key(scoped(name))
def second_caller(name):
    return key(scoped(name))
a = first_caller("paris.a")
print(a == second_caller("paris.a"), a != first_caller("paris.b"))
"""


def test_cache_key_holds_op_names_but_not_callers():
    # An executable cached by a build without the engine's named scopes
    # must not be loaded in place of one with them; which caller compiled
    # a program first must not change its key.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _KEY], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split() == ["True", "True"]
