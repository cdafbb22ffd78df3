"""``jax.profiler`` hooks: wave steps, the capture itself, and the tables
that give each device op of a capture its named scope.

The span tracer times HOST stages (queue/pack/device-wait/collect); what it
cannot see is where the device time itself goes.  Three pieces close that:

* :func:`step` brackets each serve/train wave launch with
  ``jax.profiler.StepTraceAnnotation``, so each wave shows up as one step
  of the captured trace.  It fires while the span tracer is enabled or a
  profile directory is configured, and is a shared null context otherwise;
* :func:`start`/:func:`stop` drive a device trace capture into the
  configured directory (``obs.configure(profile_dir=...)``, or
  ``-S PROFILE_DIR=...`` through the CLI, which starts the capture before
  the command runs and stops it on exit);
* :func:`note` (called by the wave scheduler and the serve engine while the
  tracer is enabled) remembers each jitted entry point launched, with the
  abstract shapes of its arguments; :func:`scope_tables`, called after the
  captured window, compiles them again (a compile-cache hit) and reads each
  instruction's innermost named scope (:data:`SCOPES`) from the HLO
  metadata.  The trace's op events carry no scope, but they carry their
  module (the device plane's ``XLA Modules`` line) and their instruction
  name, which these tables join on.

With no directory configured and the tracer off every hook is a no-op.
With a directory configured a failed capture raises: a profile that was
asked for and silently not taken would be read as "no device time".
"""
from __future__ import annotations

import contextlib
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

# the program's named scopes (``jax.named_scope`` in ``core/cv.cv_cell``):
# the Gram's distance matrix, the per-gamma kernel epilogue, the solve, and
# inside the least-squares solve (``core/solvers/least_squares``) its
# factorisation and its solves for the lambda path
SCOPES: Tuple[str, ...] = ("cv.d2", "cv.epilogue", "cv.solve",
                           "cv.ls_factor", "cv.ls_path")

# process-global profile directory; None = no capture
_PROFILE_DIR: Optional[str] = None
_ACTIVE = False
_TRACER = None                 # the span tracer (bound by ``repro.obs``)
_NULL = contextlib.nullcontext()
# (entry point, abstract arguments) -> (entry point, args, kwargs)
_PROGRAMS: Dict[Any, Tuple[Any, tuple, dict]] = {}


def bind_tracer(tracer) -> None:
    """The tracer whose ``enabled`` flag also turns :func:`step` on."""
    global _TRACER
    _TRACER = tracer


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
    up as step ``num`` of ``name`` in the captured trace.  A shared null
    context unless the tracer is enabled or a profile directory is
    configured (the hot path pays two global reads).
    """
    if _PROFILE_DIR is None and not _TRACER.enabled:
        return _NULL
    return jax.profiler.StepTraceAnnotation(name, step_num=num)


def _abstract(x):
    if isinstance(x, (jax.Array, np.ndarray, jax.ShapeDtypeStruct)):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


def note(fn, *args, **kwargs) -> None:
    """Remember a launch of the jitted ``fn`` for :func:`scope_tables`:
    one dict insert per new argument shape.  Callers call this only while
    their tracer is enabled."""
    a_args = tuple(_abstract(a) for a in args)
    a_kw = tuple(sorted((k, _abstract(v)) for k, v in kwargs.items()))
    key = (fn, a_args, a_kw)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = (fn, a_args, dict(a_kw))


def clear_programs() -> None:
    _PROGRAMS.clear()


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([^\s=]+) = ')
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(op_name: str, scopes: Tuple[str, ...] = SCOPES) -> Optional[str]:
    """The innermost (rightmost) of ``scopes`` in an HLO ``op_name``
    path, wrapped in transform names or not (``vmap(cv.solve)``)."""
    best, at = None, -1
    for s in scopes:
        for m in re.finditer(r"(?<![\w.])" + re.escape(s) + r"(?![\w.])",
                             op_name):
            if m.start() > at:
                best, at = s, m.start()
    return best


def _hlo_scopes(hlo_text: str,
                scopes: Tuple[str, ...] = SCOPES) -> Dict[str, Optional[str]]:
    """{instruction: scope or None} for every instruction of a compiled
    module's HLO text, from the ``op_name`` of its metadata."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1), scopes) if op else None
    return out


def _module_name(hlo_text: str) -> str:
    head = hlo_text.split("\n", 1)[0]
    return head.split()[1].rstrip(",") if head.startswith("HloModule") \
        else head


def scope_tables(scopes: Tuple[str, ...] = SCOPES
                 ) -> Dict[str, Dict[str, str]]:
    """``{module: {instruction: scope}}`` for every program :func:`note`
    remembered, keyed by module name (``jit_train_cells``) as a trace's
    ``XLA Modules`` events name it.  Each is compiled again from its
    abstract arguments (a compile-cache hit), so call this after the
    captured window, never inside it: the time would show up as device
    idle.

    Those events add a program id that no compiled executable exposes
    (its ``fingerprint`` is another hash), so programs that share a name
    share a table, holding only the instructions whose scope they agree
    on; the rest of their ops stay unscoped.
    """
    merged: Dict[str, Dict[str, Optional[str]]] = {}
    for fn, args, kwargs in _PROGRAMS.values():
        text = fn.lower(*args, **kwargs).compile().as_text()
        table = merged.setdefault(_module_name(text), {})
        for instr, scope in _hlo_scopes(text, scopes).items():
            table[instr] = scope if table.get(instr, scope) == scope else None
    return {m: {i: s for i, s in t.items() if s is not None}
            for m, t in merged.items()}
