"""Backend-aware Pallas execution mode.

Every kernel wrapper takes an ``interpret`` knob.  Historically it defaulted
to ``True`` (safe everywhere, slow); the correct default depends on where we
run: on a real TPU the Mosaic-compiled kernel must execute natively, anywhere
else (CPU CI, GPU hosts) only the interpreter can run the kernel body.

All ``ops.py`` entry points now accept ``interpret=None`` meaning "resolve
against the actual backend at trace time" via :func:`resolve_interpret`.
Passing an explicit bool still wins (tests force ``interpret=True`` to
validate kernel bodies off-TPU).

:func:`enable_compile_cache` places JAX's persistent compilation cache for
the process entry points (``chip_smoke.py``, ``python -m repro.cli``);
nothing calls it at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/kernels/runtime.py -> <checkout>/.jax_cache
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def on_tpu() -> bool:
    """True when jax will dispatch to a real TPU backend."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve the tri-state interpret knob.

    ``None``  -> auto: native on TPU, interpreter elsewhere.
    ``True``  -> interpreter, except on TPU where native is always correct
                 (and the interpreter is not supported on device).
    ``False`` -> native Mosaic compilation (only valid on TPU).
    """
    if interpret is None:
        return not on_tpu()
    return bool(interpret) and not on_tpu()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself) and no other directory is set.  Otherwise the cache is the
    fixed, gitignored ``.jax_cache/`` of this checkout, so the next run from
    the same checkout finds it again: it never names a temp dir, a pid or a
    time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
