"""Shared kernel-runtime policy: interpret mode and the compile cache.

:func:`resolve_interpret` is the ONE place the "should Pallas run in
interpret mode?" decision lives.  It used to be re-derived as
``jax.default_backend() != "tpu"`` at six call sites (both localize
retry builders, the streaming serve step, the block-ELL backend, and
the two ``*_auto`` kernel wrappers); abftlint's sync pass exempts this
module by construction, and every other backend query in a hot path is
a finding.

Resolution order:

1. an explicit ``interpret=`` argument (tests and benchmarks pass one);
2. the ``REPRO_PALLAS_INTERPRET`` environment variable (``0``/``false``
   forces compiled, anything else forces interpret) — the escape hatch
   for forcing either mode on unusual hosts without threading a flag
   through every layer;
3. the backend default: interpret everywhere but TPU (CPU/GPU have no
   Pallas TPU backend to compile for).

The result is always a plain ``bool``, safe as a jit static argument.

:func:`use_compile_cache` places JAX's persistent compilation cache for
the entry points (``chip_smoke.py``, ``launch/serve_*.py``).
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

import jax

_ENV = "REPRO_PALLAS_INTERPRET"
_FALSY = ("0", "false", "no", "off", "")

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# one fixed path per checkout, so a later run from the same checkout finds
# what an earlier one compiled
_CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve an ``interpret`` override to a concrete bool (see module
    docstring for the precedence)."""
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get(_ENV)
    if env is not None:
        return env.strip().lower() not in _FALSY
    return jax.default_backend() != "tpu"  # abftlint: backend-query-ok


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed; otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Call before the first compile."""
    placed = os.environ.get(_CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
