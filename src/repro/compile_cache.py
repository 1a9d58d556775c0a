"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/*``) call
:func:`use_compile_cache` before they compile anything.  The placement
rule:

* ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself at import, and
  nothing is set in code — whoever runs the program decides;
* otherwise the fixed path ``<repo root>/.jax_cache`` (git-ignored).  A
  fixed path, never one built from a temp name, a pid or the time, so a
  later run of the same program finds what an earlier one compiled.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
