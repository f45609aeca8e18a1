"""Spans and counters inside the program, always on.

A span records what one layer did and when: its name, its start and
end on ``time.monotonic`` (the base of ``LocalJaxBackend.now``), the id
of the span that caused it, its attributes (job, technique, chips,
launch token), its thread, and the type of the exception that ended it.
Spans nest per thread.  A span opened on one thread on behalf of a span
open on another (a launch handing its segment to a worker thread) names
that span as its ``parent`` explicitly.

Every span is also entered as ``jax.profiler.TraceAnnotation("saturn."
+ name)``: while a profiler session is active the spans sit in its
trace beside the device's operations, on the profiler's clock.  Nothing
here waits for the device: a span around a dispatch ends when the call
returns.

A counter adds to the innermost span open on the calling thread.  JAX's
compile events are counted so, from one listener:

- ``compile.requests``, ``compile.cache_hits``: programs asked of the
  persistent compilation cache, and those found there;
- ``compile.trace_s``: seconds tracing to a jaxpr and lowering to MLIR;
- ``compile.load_s``: seconds reading a program from the cache;
- ``compile.backend_s``: seconds compiling, less the cache read that the
  compile event contains.

Only the outermost of nested compile phases is timed (a jit traced
inside another's trace is part of the outer trace).

Completed spans go to a bounded buffer that drops the oldest when full
and counts the drops; per-name totals of spans and counters are kept
apart and drop nothing.  The outermost span of a thread (a worker's
``segment``, the Trial Runner's ``profile``) also keeps the totals of
every span and counter beneath it on that thread, readable while it is
open.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
from jax.profiler import TraceAnnotation

PREFIX = "saturn."
CAPACITY = 1 << 15
COMPILE_SECONDS = ("compile.trace_s", "compile.load_s", "compile.backend_s")
_now = time.monotonic


class Span:
    """One span; a context manager from :meth:`Tracer.span`.

    ``tree_spans`` (name -> [count, seconds]) and ``tree_counts`` are
    kept on the outermost span of a thread only: the spans closed beneath
    it, and the counters of it and every span beneath it."""

    __slots__ = ("name", "id", "parent", "root", "root_id", "attrs",
                 "thread", "t0", "t1", "error", "counters", "tree_spans",
                 "tree_counts", "_tracer", "_root", "_ann")

    def __init__(self, tracer: "Tracer", name: str, parent: Optional[int],
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.id = next(tracer._ids)
        self.parent = parent
        self.attrs = attrs
        self.root = self.root_id = None
        self.thread = None
        self.t0 = self.t1 = None
        self.error: Optional[str] = None
        self.counters: Optional[Dict[str, float]] = None
        self.tree_spans: Optional[Dict[str, List]] = None
        self.tree_counts: Optional[Dict[str, float]] = None
        self._root = self._ann = None

    @property
    def seconds(self) -> float:
        """Length; up to now while the span is open."""
        return (_now() if self.t1 is None else self.t1) - self.t0

    def __enter__(self) -> "Span":
        stack = self._tracer._local.stack
        if stack:
            top = stack[-1]
            if self.parent is None:
                self.parent = top.id
            self._root = top._root
        else:
            self._root = self
        self.root, self.root_id = self._root.name, self._root.id
        self.thread = threading.current_thread().name
        stack.append(self)
        self._ann = TraceAnnotation(PREFIX + self.name)
        self._ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, etype, exc, tb) -> bool:
        self.t1 = _now()
        self._ann.__exit__(etype, exc, tb)
        self._ann = None
        if etype is not None:
            self.error = etype.__name__
        self._tracer._close(self)
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"root={self.root!r}, t0={self.t0}, t1={self.t1}, "
                f"error={self.error!r})")


class _Local(threading.local):
    def __init__(self):
        self.stack: List[Span] = []


class Tracer:
    """Spans and counters of one process (see the module's docstring)."""

    def __init__(self, capacity: int = CAPACITY):
        self._ids = itertools.count(1)
        self._local = _Local()
        self._lock = threading.Lock()
        self._done: collections.deque = collections.deque(maxlen=capacity)
        self._totals: Dict[str, List] = {}
        self._counters: Dict[str, float] = {}
        self.dropped = 0

    def span(self, name: str, parent: Union[Span, int, None] = None,
             **attrs) -> Span:
        """A span named ``name``, to be entered with ``with``.
        ``parent`` (a span or its id) links a span to one open on
        another thread; by default the parent is the innermost span open
        on this thread."""
        if isinstance(parent, Span):
            parent = parent.id
        return Span(self, name, parent, attrs)

    def count(self, name: str, n: float = 1) -> None:
        """Add ``n`` to counter ``name`` of the innermost span open on
        the calling thread (and to the process's total)."""
        stack = self._local.stack
        if stack:
            sp = stack[-1]
            if sp.counters is None:
                sp.counters = {}
            sp.counters[name] = sp.counters.get(name, 0) + n
            root = sp._root
            if root.tree_counts is None:
                root.tree_counts = {}
            root.tree_counts[name] = root.tree_counts.get(name, 0) + n
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def _close(self, sp: Span) -> None:
        stack = self._local.stack
        if stack and stack[-1] is sp:
            stack.pop()
        elif sp in stack:
            stack.remove(sp)
        root, sp._root = sp._root, None
        dt = sp.t1 - sp.t0
        if root is not sp:
            if root.tree_spans is None:
                root.tree_spans = {}
            tot = root.tree_spans.setdefault(sp.name, [0, 0.0])
            tot[0] += 1
            tot[1] += dt
        with self._lock:
            if len(self._done) == self._done.maxlen:
                self.dropped += 1
            self._done.append(sp)
            tot = self._totals.setdefault(sp.name, [0, 0.0])
            tot[0] += 1
            tot[1] += dt

    def spans(self) -> List[Span]:
        """The completed spans still in the buffer, oldest first."""
        with self._lock:
            return list(self._done)

    def totals(self) -> Dict[str, Tuple[int, float]]:
        """Per span name: how many completed, and their seconds."""
        with self._lock:
            return {k: (v[0], v[1]) for k, v in self._totals.items()}

    def counters(self) -> Dict[str, float]:
        """Per counter: the process's total."""
        with self._lock:
            return dict(self._counters)


def compile_seconds(counts: Optional[Dict[str, float]]) -> float:
    """Tracing, lowering, cache reads and compiling in ``counts``."""
    return sum((counts or {}).get(k, 0.0) for k in COMPILE_SECONDS)


def span_totals(sp: Span) -> Dict[str, Dict[str, float]]:
    """Per span name beneath the outermost span ``sp``: count and
    seconds."""
    return {k: {"n": v[0], "s": v[1]}
            for k, v in (sp.tree_spans or {}).items()}


TRACER = Tracer()
span = TRACER.span
count = TRACER.count
spans = TRACER.spans
totals = TRACER.totals
counters = TRACER.counters

# ---------------------------------------------------- JAX compile events
_COUNTED = {"/jax/compilation_cache/compile_requests_use_cache":
            "compile.requests",
            "/jax/compilation_cache/cache_hits": "compile.cache_hits"}
_BACKEND = "/jax/core/compile/backend_compile_duration"
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration":
           "compile.trace_s",
           _BACKEND: "compile.backend_s"}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class _Phases(threading.local):
    def __init__(self):
        self.open: List[str] = []     # compile phases open, outermost first
        self.loaded = 0.0             # cache reads inside the open compile


_phases = _Phases()


def _on_event(event: str, **_) -> None:
    name = _COUNTED.get(event)
    if name is not None:
        count(name)


def _on_phase_start(event: str, value, **_) -> None:
    # JAX records a phase's start as a scalar, its length on its end
    if event in _PHASES:
        _phases.open.append(event)


def _on_duration(event: str, seconds: float, **_) -> None:
    ph = _phases
    name = _PHASES.get(event)
    if name is not None:
        if ph.open:
            ph.open.pop()
        if ph.open:               # inside an outer phase, timed there
            return
        if event == _BACKEND:     # the compile event wraps the cache read
            seconds, ph.loaded = seconds - ph.loaded, 0.0
        count(name, max(0.0, seconds))
    elif event == _RETRIEVAL and ph.open == [_BACKEND]:
        ph.loaded += seconds
        count("compile.load_s", seconds)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_scalar_listener(_on_phase_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)
