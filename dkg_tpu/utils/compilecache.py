"""Where this checkout keeps what it caches between processes.

One rule, stated once: ``JAX_COMPILATION_CACHE_DIR`` if it is set —
JAX reads that variable itself, so nothing is configured in code and
no other directory is ever chosen — otherwise ``.jax_cache/`` at the
root of the checkout (git-ignored).  The path is part of the compile
cache's key, so it is fixed, never a temp dir.  The fixed-base table
store (groups/precompute.py) and the AOT executable store
(service/aot.py) live in sub-directories of the same root, so wiping
one wipes all and whoever places the cache gets all three back.
"""

from __future__ import annotations

import os
import pathlib

from . import envknobs

_CHECKOUT_DEFAULT = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_root() -> str:
    """The one directory; the table and executable stores sit under it."""
    return envknobs.string(
        "JAX_COMPILATION_CACHE_DIR", "JAX persistent compile cache directory"
    ) or str(_CHECKOUT_DEFAULT)


def enable() -> str:
    """Switch JAX's persistent compile cache on for this process and
    the processes it starts (they inherit the variable), and return its
    directory.  Only ever a ``jax.config.update`` — it must not
    initialise a backend (callers run it before platform forcing)."""
    root = cache_root()
    if root == str(_CHECKOUT_DEFAULT):
        import jax

        os.environ["JAX_COMPILATION_CACHE_DIR"] = root
        jax.config.update("jax_compilation_cache_dir", root)
    return root
