"""Launchers: production mesh, multi-pod dry-run, roofline, train/prune CLIs."""
from __future__ import annotations

import os

#: the checkout root (``src/repro/launch/__init__.py`` is three levels down)
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Each launcher's ``main`` (and ``chip_smoke.py``) calls this at start-up,
    never at import.  ``JAX_COMPILATION_CACHE_DIR``, which JAX reads
    itself, wins when set, and nothing else is set here.  Otherwise the
    cache is ``.jax_cache/`` at the checkout root: a fixed path, because
    the path is part of what a later process looks entries up by.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
