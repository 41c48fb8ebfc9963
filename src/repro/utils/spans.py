"""Host spans on the profiler's clock, and the phase names of compiled
programs.

``span(name, into, key, counts, **stats)`` marks one stretch of host
work: a ``jax.profiler.TraceAnnotation`` (so a profiler trace shows it on
the same clock as the device's ops, with ``stats`` as its arguments),
its wall milliseconds added to ``into[key]``, and, while it is the
innermost open span with ``counts``, every JAX trace and backend compile
charged to ``counts["traces.<name>"]`` / ``counts["compiles.<name>"]``
(a compile the persistent cache answered is no compile).  Spans nest
per thread.  With the profiler off a span costs a few microseconds.

``scoped(name, fn)`` is ``fn`` traced under ``jax.named_scope(name)``:
the name reaches every HLO op ``fn`` emits (its ``op_name`` metadata,
forward and transpose), so device time can be read by phase.
"""
from __future__ import annotations

import functools
import threading
import time
from typing import Callable, Dict, Optional

import jax

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_open = threading.local()
_listening = False
_install_lock = threading.Lock()


def _charge(kind: str, step: int = 1) -> None:
    for name, counts in reversed(getattr(_open, "stack", ())):
        if counts is not None:
            k = f"{kind}.{name}"
            counts[k] = counts.get(k, 0) + step
            return


def _on_duration(event: str, duration: float, **_) -> None:
    if event == TRACE_EVENT:
        _charge("traces")
    elif event == COMPILE_EVENT:
        _charge("compiles")


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:       # fires inside the compile it answers
        _charge("compiles", -1)


def _listen() -> None:
    global _listening
    with _install_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _listening = True


class span:
    """``with span("engine.unpack", timing, "unpack_ms", counters):``;
    after the block, ``t0`` and ``t1`` hold its ``time.perf_counter()``
    bounds."""

    __slots__ = ("name", "into", "key", "counts", "_ann", "t0", "t1")

    def __init__(self, name: str, into: Optional[Dict[str, float]] = None,
                 key: Optional[str] = None,
                 counts: Optional[Dict[str, int]] = None, **stats):
        self.name, self.into, self.key, self.counts = name, into, key, counts
        self._ann = jax.profiler.TraceAnnotation(name, **stats)

    def __enter__(self) -> "span":
        if self.counts is not None and not _listening:
            _listen()
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        stack.append((self.name, self.counts))
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _open.stack.pop()
        if self.into is not None:
            self.into[self.key] = self.into.get(self.key, 0.0) + \
                (self.t1 - self.t0) * 1e3


def scoped(name: str, fn: Callable) -> Callable:
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return run
