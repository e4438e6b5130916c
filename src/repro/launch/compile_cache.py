"""Placement of JAX's persistent compilation cache for every entry point.

A full-width step compiles in tens of seconds and one run emits several step
shapes, so the launchers (``launch/train.py``, ``launch/serve.py``), the
benchmark harness and ``chip_smoke.py`` all keep compiled programs on disk.
``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself, wins when it is set;
otherwise the cache lives at one fixed path inside the checkout
(``<repo>/.jax_cache``, git-ignored).  The directory is part of what makes a
later run hit, so it is never derived from a temp dir, a pid or the clock.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point the persistent compilation cache at its one directory; returns
    the directory.  Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
