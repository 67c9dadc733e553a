"""Where JAX's persistent compilation cache lives.

Every step program is one XLA executable that takes seconds to minutes to
compile, and a fresh process (a benchmark run, ``chip_smoke.py``, a respawned
serving worker) would otherwise compile all of them again. The directory is
part of the cache key's environment, so it must not move between runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX already uses it; nothing is set
    in code, so a deployment places the cache from outside;
  * otherwise ``<checkout>/.jax_cache`` — one fixed, git-ignored path beside
    the package, never derived from a temp dir, a pid or the time.

A process held to the CPU (``JAX_PLATFORMS=cpu``: tests, rehearsals) gets no
default directory: its compiles are short, and XLA:CPU logs a page of
machine-feature warnings for every executable it reads back.
"""

import os

import jax

__all__ = ["place", "directory", "entries"]

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def place():
    """Point JAX at the default directory unless the environment already
    placed the cache. Called once, from ``paddle_tpu/__init__.py``."""
    if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)


def directory():
    """The cache directory in use (None when the cache is off)."""
    return jax.config.jax_compilation_cache_dir


def entries():
    """(count, bytes) of the executables in the cache directory now."""
    d = directory()
    if not d or not os.path.isdir(d):
        return 0, 0
    sizes = [os.path.getsize(os.path.join(d, name))
             for name in os.listdir(d) if name.endswith("-cache")]
    return len(sizes), sum(sizes)
