"""Persistent XLA compilation cache: placed from outside.

The contract (mxnet_tpu.config.enable_compile_cache, called by the package
bootstrap):

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads it itself and importing
  mxnet_tpu sets no cache directory in code;
* unset — the cache lives at one fixed path inside the checkout
  (``config.COMPILE_CACHE_DIR``, git-ignored), never under ``~`` or a
  temporary name: the path is part of the cache key;
* ``MXNET_COMPILE_CACHE=0`` — off.

The cold/warm drill runs the same jit twice against the env-placed
directory: the first compile writes an entry, and after the in-memory
executable cache is dropped the second is served from disk (observed via
jax's own cache-hit monitoring event).  It runs in a SUBPROCESS: it must
call ``jax.clear_caches()``, which would throw away every compiled program
the rest of the suite has accumulated in this process.
"""
import os
import subprocess
import sys

import jax

import mxnet_tpu as mx  # noqa: F401  (bootstrap places the default cache)
from mxnet_tpu import config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_DRILL = r"""
import os, sys, time
import numpy as np
import jax
import jax.monitoring

placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
updates = []
_update = jax.config.update
jax.config.update = lambda k, v: (updates.append(k), _update(k, v))[1]
import mxnet_tpu  # bootstrap
jax.config.update = _update
assert jax.config.jax_compilation_cache_dir == placed, \
    jax.config.jax_compilation_cache_dir
assert "jax_compilation_cache_dir" not in updates, updates

import jax.numpy as jnp
events = []
jax.monitoring.register_event_listener(lambda e, **kw: events.append(e))

def f(x):
    return jnp.sin(x) @ jnp.cos(x.T) + jnp.tanh(x).sum()

x = jnp.asarray(np.random.RandomState(0).rand(64, 64), jnp.float32)
# both calls from one line: the key holds the names and source lines a
# device profile is read by (config.enable_compile_cache), the caller's
# among them
for again in (False, True):
    if again:
        events.clear()
        jax.clear_caches()  # drop in-memory executables; disk cache remains
    out = jax.jit(f)(x).block_until_ready()
    if not again:
        cold = out
        entries = [e for e in os.listdir(placed) if e.endswith("-cache")]
        assert entries, "first compile wrote no cache entry"
warm = out
assert jax.config.jax_compilation_cache_include_metadata_in_key
assert "/jax/compilation_cache/cache_hits" in events, \
    "second compile missed the persistent cache: %s" % [
        e for e in events if "cache" in e]
np.testing.assert_allclose(np.asarray(warm), np.asarray(cold), atol=1e-6)
print("DRILL OK entries=%d" % len(entries))
"""


def _run(code, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "MXNET_COMPILE_CACHE")}
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=REPO,
        env=dict(base, JAX_PLATFORMS="cpu", XLA_FLAGS="", **env))


def test_env_places_the_cache_and_a_second_compile_hits_it(tmp_path):
    r = _run(_DRILL, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
             JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DRILL OK" in r.stdout, r.stdout


def test_unset_env_means_one_fixed_path_inside_the_checkout():
    assert config.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # this process: whoever started the suite may have placed the cache
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    assert jax.config.jax_compilation_cache_dir == \
        (placed or config.COMPILE_CACHE_DIR)
    # a fresh process with nothing exported
    r = _run("import jax, mxnet_tpu; "
             "print(jax.config.jax_compilation_cache_dir)")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.split()[-1] == config.COMPILE_CACHE_DIR


def test_off_switch_leaves_the_cache_unset():
    assert config.get("MXNET_COMPILE_CACHE") is True    # default: on
    r = _run("import jax, mxnet_tpu; "
             "print(jax.config.jax_compilation_cache_dir)",
             MXNET_COMPILE_CACHE="0")
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.split()[-1] == "None"
