"""Where JAX keeps compiled programs between processes.

Entry points call :func:`enable_compile_cache` once, before their first
jitted call; importing this module changes nothing.  The simulator's scan
and the trainer's round take seconds to minutes to compile on a TPU, and a
cache at a fixed path lets the next run of the same shapes skip that.
"""

from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed
#: directory of the checkout (under the git-ignored ``artifacts/``).  It is
#: found three levels above this file, so it assumes the package runs from
#: the checkout's ``src/`` (``PYTHONPATH=src`` or an editable install); a
#: copy installed into site-packages should set the variable instead.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "artifacts",
    "jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets no other directory; otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
