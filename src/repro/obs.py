"""Spans and counters: where a sweep's wall time goes, measured where it runs.

``span(name)`` times a block on the calling thread and writes the same
name into the JAX profiler's trace (``jax.profiler.TraceAnnotation``), so
in a ``jax.profiler`` trace each span sits on its host thread's line, on
the clock of the device timeline. ``count(name, n)`` adds to a counter.
Both add to the :class:`Recorder` active in the calling context
(``Controller.run_many`` activates one per call with :func:`recording`);
outside one a span only annotates the profiler and a count is dropped.
The profiler trace is the only export.

A span's parent is the innermost span open on the same thread when it
opened. Names are ``<layer>.<step>`` (``store.read``, ``nsa.tables``);
``sweep`` and ``reset`` are left to callers that time whole sweeps.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import threading
import time
from typing import Dict, Iterator, Optional, Tuple

from jax.profiler import TraceAnnotation

#: the ``jax.monitoring`` event of one XLA backend compilation
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class SpanTotal:
    seconds: float = 0.0
    calls: int = 0


class Recorder:
    """Span totals and counters of one recording, kept in memory.

    ``totals`` maps ``(name, parent)`` to the span's summed seconds and
    calls (``parent`` None for a span opened outside any other).
    ``seconds`` is the recording's own wall time, set when it ends.
    """

    def __init__(self):
        self.totals: Dict[Tuple[str, Optional[str]], SpanTotal] = {}
        self._counts: Dict[str, int] = {}
        self._lock = threading.Lock()
        self.seconds = 0.0

    def add_span(self, name: str, parent: Optional[str],
                 seconds: float) -> None:
        with self._lock:
            t = self.totals.get((name, parent))
            if t is None:
                t = self.totals[(name, parent)] = SpanTotal()
            t.seconds += seconds
            t.calls += 1

    def add_count(self, name: str, n: int) -> None:
        with self._lock:
            self._counts[name] = self._counts.get(name, 0) + int(n)

    def spans(self) -> Dict[str, float]:
        """Seconds per span name, over every parent."""
        out: Dict[str, float] = {}
        with self._lock:
            for (name, _), t in self.totals.items():
                out[name] = out.get(name, 0.0) + t.seconds
        return out

    def calls(self) -> Dict[str, int]:
        """Closed spans per name, over every parent."""
        out: Dict[str, int] = {}
        with self._lock:
            for (name, _), t in self.totals.items():
                out[name] = out.get(name, 0) + t.calls
        return out

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def top_level_s(self) -> float:
        """Seconds of the spans opened outside any other span."""
        with self._lock:
            return sum(t.seconds for (_, parent), t in self.totals.items()
                       if parent is None)


_recorder: contextvars.ContextVar[Optional[Recorder]] = \
    contextvars.ContextVar("repro_obs_recorder", default=None)
#: names of the spans open in this context, innermost last (a new thread
#: starts with an empty context, so each thread keeps its own stack)
_open: contextvars.ContextVar[Tuple[str, ...]] = \
    contextvars.ContextVar("repro_obs_open", default=())


class span:
    """Context manager timing one block; the handle's ``seconds`` holds
    the block's wall time once it has closed."""

    __slots__ = ("name", "parent", "seconds", "_rec", "_token", "_annot",
                 "_t0")

    def __init__(self, name: str):
        self.name = name
        self.parent: Optional[str] = None
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._rec = _recorder.get()
        stack = _open.get()
        self.parent = stack[-1] if stack else None
        self._token = _open.set(stack + (self.name,))
        self._annot = TraceAnnotation(self.name)
        self._annot.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annot.__exit__(*exc)
        _open.reset(self._token)
        if self._rec is not None:
            self._rec.add_span(self.name, self.parent, self.seconds)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the active recorder's counter ``name``."""
    rec = _recorder.get()
    if rec is not None:
        rec.add_count(name, n)


_listening = False
_listen_lock = threading.Lock()


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        count("jax.compiles")


def _listen_for_compiles() -> None:
    """Register the compile counter with ``jax.monitoring`` once; it
    counts into the recorder active on the compiling thread."""
    global _listening
    with _listen_lock:
        if not _listening:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Make a fresh :class:`Recorder` the active one in this context for
    the ``with`` block; spans opened inside start a new parent stack."""
    _listen_for_compiles()
    rec = Recorder()
    token = _recorder.set(rec)
    open_token = _open.set(())
    t0 = time.perf_counter()
    try:
        yield rec
    finally:
        rec.seconds = time.perf_counter() - t0
        _open.reset(open_token)
        _recorder.reset(token)
