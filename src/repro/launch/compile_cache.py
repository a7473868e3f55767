"""Where the command-line entry points keep JAX's persistent compile cache.

``use_compile_cache()`` is called from the ``main()`` of each entry point
(``repro.launch.eigen``, ``repro.launch.serve``, ``chip_smoke.py``) and
from nowhere else: importing the library or running the tests never
turns the cache on.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: a fixed path, since the cache directory is part
# of each entry's key and a directory that moves never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and the cache stays there, untouched.  Otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
