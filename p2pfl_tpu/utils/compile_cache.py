"""Where XLA's persistent compile cache lives — decided in one place.

Every entry point (``run``, ``p2p.launch`` parent and child,
``parallel.dcn``, ``chip_smoke.py``, ``benchmark/run.py`` and
``__graft_entry__``) calls :func:`enable` first thing. A cold 64-node round program compiles for most of a
minute on a v5e; the machine a run lands on may keep nothing between
calls but one directory, and the directory's path is part of the cache
key — so the path is either the one the operator names or a fixed one,
never a temporary name, a pid or the time.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout this package was imported from (``.jax_cache`` is in
#: its ``.gitignore``)
_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable() -> str:
    """Return the cache directory in use, choosing it if nobody has.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
    function sets nothing. Unset: ``<checkout>/.jax_cache``, exported
    through the environment so child processes land in the same place,
    and pushed into ``jax``'s config (which read the environment when
    it was imported). Never initialises a backend.
    """
    path = os.environ.get(ENV)
    if path:
        return path
    path = str(_CHECKOUT / ".jax_cache")
    os.environ[ENV] = path
    jax.config.update("jax_compilation_cache_dir", path)
    return path
