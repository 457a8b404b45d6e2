"""The program's own spans and counters, on only while a JAX profiler
session runs.

``span(name, **attrs)`` marks a stretch of host code. Outside a profiler
session it returns one shared object that does nothing. Inside one
(``jax.profiler.start_trace`` ... ``stop_trace``, or a profiler server being
sampled) it enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so the
span lands in the trace on the same clock as the device's operations, and
keeps ``(name, parent index, start, end, attrs)`` on the host clock
(``time.perf_counter``) in memory; the parent is the innermost span open on
the same thread. ``count(name, n)`` adds to a counter, also only inside a
session. While a session runs, every ``/jax/core/compile/*`` duration JAX
reports (tracing, lowering, compiling or loading from the persistent cache)
is added to the counter ``jit.s`` and to ``jit.s/<innermost open span>``,
which names the step that compiled. ``records()`` returns what was kept;
``clear()`` empties it.

The state is per process, as the profiler session it follows is. Spans go
only around host code: never inside a function that JAX traces. They add no
synchronisation with the device: a span that ends at a ``block_until_ready``
ends at one its code already had.
"""

from __future__ import annotations

import threading
import time

import jax
from jax.profiler import TraceAnnotation

CAP = 1 << 20            # spans kept; later ones only add to "obs.dropped"
COMPILE_EVENTS = "/jax/core/compile/"

_on = TraceAnnotation.is_enabled
_lock = threading.Lock()
_local = threading.local()
_spans: list[list] = []  # [name, parent index, start, end, attrs]
_counters: dict[str, float] = {}


def _stack() -> list[tuple[int, list]]:
    """This thread's open spans, innermost last: (index or -1, record)."""
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("_name", "_attrs", "_ann", "_rec")

    def __init__(self, name: str, attrs: dict):
        self._name, self._attrs = name, attrs

    def __enter__(self):
        self._ann = TraceAnnotation(self._name, **self._attrs)
        self._ann.__enter__()
        stack = _stack()
        rec = self._rec = [self._name, stack[-1][0] if stack else -1, 0.0, None,
                           self._attrs or None]
        with _lock:
            if len(_spans) < CAP:
                i = len(_spans)
                _spans.append(rec)
            else:
                i = -1
                _counters["obs.dropped"] = _counters.get("obs.dropped", 0) + 1
        stack.append((i, rec))
        rec[2] = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self._rec[3] = time.perf_counter()
        _stack().pop()
        self._ann.__exit__(*exc)
        return False


def span(name: str, **attrs):
    """A context manager over host code: recorded inside a profiler
    session, a shared no-op outside one."""
    return _Span(name, attrs) if _on() else _OFF


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` (inside a profiler session only)."""
    if _on():
        with _lock:
            _counters[name] = _counters.get(name, 0) + n


def _on_duration(event: str, seconds: float, **_) -> None:
    if not event.startswith(COMPILE_EVENTS) or not _on():
        return
    stack = _stack()
    inner = stack[-1][1][0] if stack else None
    with _lock:
        _counters["jit.s"] = _counters.get("jit.s", 0.0) + seconds
        if inner is not None:
            key = "jit.s/" + inner
            _counters[key] = _counters.get(key, 0.0) + seconds


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def records() -> dict:
    """``{"spans": [(name, parent index or -1, start, end or None, attrs or
    None), ...], "counters": {name: value}}``, times in ``perf_counter``
    seconds; a span still open has no end."""
    with _lock:
        return {"spans": [tuple(r) for r in _spans], "counters": dict(_counters)}


def clear() -> None:
    """Forget every span and counter kept so far."""
    with _lock:
        _spans.clear()
        _counters.clear()
