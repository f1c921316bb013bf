"""Program spans and counters on the profiler's clock.

``span(name)`` times a piece of the served path with ``time.perf_counter``
(its duration is ``.s`` after exit). Under a profiler session it also
enters ``jax.profiler.TraceAnnotation(name)``, so the span lands in the
same trace as the device ops, on the same clock. A span opened inside
another is its child.

While a profiler session is collecting, spans are also kept in memory, per
name: count ``n``, total seconds ``s``, self seconds ``self_s`` (total minus
the children's time), the ``parent``'s name, and the counters that
``count(key, n)`` added inside the span or any span below it. A session of
this record starts with the first root span opened while the profiler
collects, and clears the previous one; ``window()`` returns it. Each thread
keeps its own span stack.

``to_device``/``to_host`` are ``jnp.asarray``/``np.asarray`` that count the
bytes crossing (``h2d_bytes``/``d2h_bytes``). A span adds no
synchronisation: one that should include device work ends at a host copy
(``to_host``) that waits for it.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax._src.lib import _profiler

_collecting = _profiler.TraceMe.is_enabled    # True inside a profiler session

_lock = threading.Lock()
_local = threading.local()
_window: Dict[str, dict] = {}
_session_open = False


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _record(name: str, parent: Optional[str]) -> dict:
    """The in-memory record of ``name`` (call with ``_lock`` held)."""
    rec = _window.get(name)
    if rec is None:
        rec = _window[name] = {"n": 0, "s": 0.0, "self_s": 0.0,
                               "parent": parent}
    return rec


class span:
    """Context manager timing one piece of work (see the module doc)."""

    def __init__(self, name: str):
        self.name, self.s = name, 0.0

    def __enter__(self) -> "span":
        global _session_open
        stack = _stack()
        parent = stack[-1] if stack else None
        on = _collecting()
        if parent is not None:
            self.kept = on and parent.kept
        else:
            self.kept = on
            if on or _session_open:      # tracing off: no lock taken
                with _lock:
                    if on and not _session_open:
                        _window.clear()
                    _session_open = on
        if self.kept:
            with _lock:
                _record(self.name, parent and parent.name)
        self._child_s = 0.0
        stack.append(self)
        self._ann = None          # outside a session it would record nothing
        if on:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.s = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = _stack()
        stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            parent._child_s += self.s
        if self.kept:
            with _lock:
                rec = _record(self.name, parent and parent.name)
                rec["n"] += 1
                rec["s"] += self.s
                rec["self_s"] += self.s - self._child_s


def spanned(name: str):
    """Decorator: run the function inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*a, **kw):
            with span(name):
                return fn(*a, **kw)
        return inner
    return wrap


def _kept() -> bool:
    """Whether the innermost open span is kept."""
    stack = getattr(_local, "stack", None)
    return bool(stack) and stack[-1].kept


def count(key: str, n: int) -> None:
    """Add ``n`` to counter ``key`` of the innermost open span and of every
    span enclosing it (once per name). A no-op unless the span is kept."""
    if not _kept():
        return
    stack = _local.stack
    with _lock:
        for name in {sp.name for sp in stack if sp.kept}:
            rec = _record(name, None)
            rec[key] = rec.get(key, 0) + int(n)


def to_device(x) -> jax.Array:
    """``jnp.asarray(x)``, counted as ``h2d_bytes`` where ``x`` is on the
    host."""
    if _kept() and not isinstance(x, jax.Array):
        count("h2d_bytes", np.asarray(x).nbytes)
    return jnp.asarray(x)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)`` of a device array (waits for it), counted as
    ``d2h_bytes``."""
    out = np.asarray(x)
    count("d2h_bytes", out.nbytes)
    return out


def window() -> Dict[str, dict]:
    """The last session: ``{span name: {"n", "s", "self_s", "parent",
    counters...}}`` (a copy). Read after the profiler stopped, it also
    closes the session, so the next one starts afresh."""
    global _session_open
    with _lock:
        if not _collecting():
            _session_open = False
        return {k: dict(v) for k, v in _window.items()}
