"""Persistent XLA compilation cache for the repository's entry points.

Scripts (``chip_smoke.py``, ``examples/serve_lm.py``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once, before their first compile. Library
modules never call it, so importing ``repro`` changes no JAX setting.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory. Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the path is part of
the cache key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.

    The cache is keyed on the programs' op metadata too, with source paths
    taken relative to the checkout. By default JAX leaves metadata out of
    the key, so a program whose named scopes changed would load an
    executable compiled from older code, and a profile would show the old
    scopes (or none) on its ops."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(ROOT)) + "/")
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
