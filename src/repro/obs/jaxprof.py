"""Optional ``jax.profiler`` hooks, gated by the ``PROFILE_DIR`` config key.

The span tracer times HOST stages (queue/pack/device-wait/collect); what it
cannot see is where the device time itself goes.  When a profile directory
is configured (``obs.configure(profile_dir=...)``, or ``-S PROFILE_DIR=...``
through the CLI), wave launches are bracketed with
``jax.profiler.StepTraceAnnotation`` so each serve/train wave shows up as
one step in the captured trace, and :func:`start`/:func:`stop` drive the
device trace capture itself.

With no directory configured every hook is a no-op.  With one configured a
failed capture raises: a profile that was asked for and silently not taken
would be read as "no device time".
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax

# process-global profile directory; None = all hooks are no-ops
_PROFILE_DIR: Optional[str] = None
_ACTIVE = False


def configure(profile_dir: Optional[str]) -> None:
    global _PROFILE_DIR
    _PROFILE_DIR = profile_dir


def profile_dir() -> Optional[str]:
    return _PROFILE_DIR


def active() -> bool:
    """True while a device trace capture is running."""
    return _ACTIVE


def start() -> bool:
    """Begin a device trace capture into the configured directory.
    Returns False (no-op) when unconfigured or already active."""
    global _ACTIVE
    if _PROFILE_DIR is None or _ACTIVE:
        return False
    jax.profiler.start_trace(_PROFILE_DIR)
    _ACTIVE = True
    return True


def stop() -> bool:
    global _ACTIVE
    if not _ACTIVE:
        return False
    _ACTIVE = False
    jax.profiler.stop_trace()
    return True


def step(name: str, num: int):
    """Context manager bracketing one wave launch as a profiler step.

    ``with jaxprof.step("serve_wave", seq): dec = evaluate(...)`` — shows
    up as step ``num`` of ``name`` in the captured trace.  Returns a
    nullcontext unless a profile directory is configured (the hot path
    pays one global read).
    """
    if _PROFILE_DIR is None:
        return contextlib.nullcontext()
    return jax.profiler.StepTraceAnnotation(name, step_num=num)
