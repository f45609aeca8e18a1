"""Instruments around the measured window, installed from the benchmark's
own files without changing the system under test: they keep a reference
to every training worker the backend starts, time the policy's plans,
record host spans around the calls into each layer, count compiles,
capture what the comparison needs from each job's first steps, and close
the window at its deadline (the next step or wait raises
:class:`WindowClosed`)."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from .reference import change_norms, leaf_norms, leaf_paths


class WindowClosed(Exception):
    """Raised inside the system under test once the window has closed."""


class CompileCounter:
    """Compile requests and persistent-cache hits, from JAX's monitoring
    events; a request that is not a hit is a compile."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            with self._lock:
                self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1

    def snapshot(self):
        with self._lock:
            return self.requests, self.hits


@dataclasses.dataclass
class Span:
    name: str
    t0: float                 # time.monotonic()
    t1: float
    thread: str


@dataclasses.dataclass
class Launch:
    worker: object            # the backend's worker thread
    job: str
    technique: str
    n_gpus: int
    devices: tuple            # the placement's device ids
    t0: float                 # time.monotonic() at launch
    clock0: float             # time.monotonic() at the backend's t = 0


class Capture:
    """Per job started from step 0 in the window: the tokens of its first
    three batches as the step received them, the per-leaf norm of the
    gradient AdamW took at step 1 (its first moment after one step, over
    ``1 - b1``), read as step 2 starts, and the per-leaf norm of the
    change of the weights after three steps, read as step 4 starts (then
    only one generation of the state is on the device)."""

    def __init__(self, spec, b1: float):
        self.spec = spec
        self.b1 = b1
        self._norms = jax.jit(leaf_norms)
        self._moved = jax.jit(functools.partial(change_norms, spec=spec))
        self.grad: Dict[object, np.ndarray] = {}
        self.change: Dict[object, np.ndarray] = {}
        self.tokens: Dict[object, List[np.ndarray]] = {}
        self.paths: Optional[List[str]] = None

    @staticmethod
    def key(seed: int) -> np.ndarray:
        return np.asarray(jax.random.PRNGKey(seed))

    def warm(self, params, opt) -> None:
        """Compile both readers for these shapes and shardings."""
        np.asarray(self._norms(opt["mu"]))
        np.asarray(self._moved(params, self.key(0)))
        self.paths = leaf_paths(params)

    def on_step(self, worker, n: int, params, opt, batch) -> None:
        if n <= 3:
            self.tokens.setdefault(worker, []).append(
                np.asarray(batch["tokens"]))
        if n == 2:
            self.grad[worker] = np.asarray(self._norms(opt["mu"])) / (
                1.0 - self.b1)
        elif n == 4:
            self.change[worker] = np.asarray(
                self._moved(params, self.key(worker.job.seed)))


class TraceMarks:
    """When a ``--trace 1`` run's trace starts and stops, anchored on
    steps: as the first job launched in the window starts its last
    ``STEPS`` steps, and as the next job to step starts its step
    ``STEPS + 1`` (its first ``STEPS`` steps, the program load among
    them, are then traced).  The traced span is one whole job switch."""
    STEPS = 5

    def __init__(self):
        self.start = threading.Event()
        self.stop = threading.Event()
        self._first = None
        self._lock = threading.Lock()

    def on_step(self, worker, n: int) -> None:
        with self._lock:
            if self._first is None:
                self._first = worker
        if worker is self._first:
            if n == worker.steps_to_run - self.STEPS + 1:
                self.start.set()
        elif self.start.is_set() and n == self.STEPS + 1:
            self.stop.set()


class StepProbe:
    """Stands in for a BuiltJob's jitted step: counts each worker's
    calls, lets the capture read the state as steps 2 and 4 start, and
    raises :class:`WindowClosed` once the window has closed."""

    def __init__(self, fn, inst: "Instruments"):
        self._fn = fn
        self._inst = inst
        self._calls: Dict[object, int] = {}

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __call__(self, params, opt, batch):
        inst = self._inst
        w = threading.current_thread()
        n = self._calls.get(w, 0) + 1
        self._calls[w] = n
        if inst.closed.is_set():
            raise WindowClosed()
        if inst.marks is not None:
            inst.marks.on_step(w, n)
        if inst.capture is not None and getattr(w, "start_step", None) == 0:
            with inst.span("capture"):
                inst.capture.on_step(w, n, params, opt, batch)
        with inst.span("first_step" if n == 1 else "step"):
            return self._fn(params, opt, batch)


class Instruments:
    def __init__(self, capture: Optional[Capture] = None,
                 annotate: bool = False,
                 marks: Optional[TraceMarks] = None):
        self.capture = capture
        self.annotate = annotate
        self.marks = marks
        self.closed = threading.Event()
        self.launches: List[Launch] = []
        self.spans: List[Span] = []
        self.plan_s: List[float] = []
        self._local = threading.local()
        self._saved = []

    @contextlib.contextmanager
    def span(self, name: str):
        ann = jax.profiler.TraceAnnotation("chipbench." + name) \
            if self.annotate else contextlib.nullcontext()
        t0 = time.monotonic()
        try:
            with ann:
                yield
        finally:
            self.spans.append(Span(name, t0, time.monotonic(),
                                   threading.current_thread().name))

    def _patch(self, owner, name, make):
        orig = getattr(owner, name)
        own = name in vars(owner)
        self._saved.append((owner, name, orig, own))
        setattr(owner, name, make(orig))

    def _spanned(self, name):
        def make(orig):
            @functools.wraps(orig)
            def wrapped(*a, **k):
                with self.span(name):
                    return orig(*a, **k)
            return wrapped
        return make

    def install(self) -> None:
        import repro.checkpoint.store as store
        from repro.core.baselines import SaturnPolicy
        from repro.core.local_backend import LocalJaxBackend
        from repro.parallelism.build import BuiltJob

        inst = self

        def launch(orig):
            @functools.wraps(orig)
            def wrapped(backend, job, entry, placement, *a, **k):
                if inst.closed.is_set():
                    raise WindowClosed()
                t0 = time.monotonic()
                with inst.span("launch"):
                    h = orig(backend, job, entry, placement, *a, **k)
                inst.launches.append(Launch(
                    h.worker, job.name, entry.technique, entry.n_gpus,
                    tuple(placement.devices), t0, backend._t0))
                return h
            return wrapped

        def built_job(orig):
            @functools.wraps(orig)
            def wrapped(backend, *a, **k):
                with inst.span("build"):
                    built = orig(backend, *a, **k)
                if not isinstance(built._step, StepProbe):
                    built._step = StepProbe(built.step, inst)
                return built
            return wrapped

        def wait_until(orig):
            @functools.wraps(orig)
            def wrapped(backend, t):
                if inst.closed.is_set():
                    raise WindowClosed()
                return orig(backend, t)
            return wrapped

        def plan(orig):
            @functools.wraps(orig)
            def wrapped(policy, *a, **k):
                depth = getattr(inst._local, "depth", 0)
                inst._local.depth = depth + 1
                t0 = time.monotonic()
                try:
                    if depth:
                        return orig(policy, *a, **k)
                    with inst.span("plan"):
                        return orig(policy, *a, **k)
                finally:
                    inst._local.depth = depth
                    if not depth:
                        inst.plan_s.append(time.monotonic() - t0)
            return wrapped

        self._patch(LocalJaxBackend, "launch", launch)
        self._patch(LocalJaxBackend, "_built_job", built_job)
        self._patch(LocalJaxBackend, "wait_until", wait_until)
        self._patch(SaturnPolicy, "plan", plan)
        self._patch(SaturnPolicy, "plan_incremental", plan)
        self._patch(BuiltJob, "init", self._spanned("init"))
        self._patch(store, "save_checkpoint", self._spanned("checkpoint"))
        self._patch(store, "load_training_state", self._spanned("restore"))

    def uninstall(self) -> None:
        while self._saved:
            owner, name, orig, own = self._saved.pop()
            if own:
                setattr(owner, name, orig)
            else:
                delattr(owner, name)
