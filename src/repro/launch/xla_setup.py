"""What the entry points set up before JAX compiles anything.

Called by the ``main`` of each entry point (``launch/serve.py``,
``launch/train.py``, ``chip_smoke.py``), never at import.
"""
from __future__ import annotations

import os
import warnings
from pathlib import Path

import jax
from jax._src import xla_bridge

CHECKOUT = Path(__file__).resolve().parents[3]


def use_persistent_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself, and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key, so a
    per-run or temporary path would never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)


def honor_bf16_rounding() -> None:
    """Make XLA round every bf16 value where the program says so.

    By default XLA may keep a bf16 intermediate in f32 inside a fusion, so
    the same model code rounds differently depending on how its neighbours
    fuse.  A Pallas TD-VMM launch is opaque to fusion and its ``jnp``
    counterpart is not, so on a TPU the two backends fused their
    surroundings differently and their token streams drifted apart, one bf16
    ulp at a time.  XLA reads its flags when the backend starts, so this
    must run before the first device use (later, as when a test calls an
    entry point's ``main``, it only warns); an explicit setting of the flag
    in ``XLA_FLAGS`` is left alone."""
    flags = os.environ.get("XLA_FLAGS", "")
    if xla_bridge.backends_are_initialized():
        if "xla_allow_excess_precision=false" not in flags:
            warnings.warn("JAX backend already running: bf16 rounding "
                          "follows XLA's default", stacklevel=2)
        return
    if "xla_allow_excess_precision" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_allow_excess_precision=false".strip())
