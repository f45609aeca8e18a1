"""The system's own spans and counters (``repro.tracing``), as the
per-layer metrics read them.  Only spans under a worker's ``segment``
(the window) or under the Trial Runner's ``profile`` (set-up) count, and
only those that ended without an exception: the program loads that set-up
makes outside the system, and the step the deadline cuts, are left out.
Where the program records no such span (a system without the tracer
included), each reading is None."""
from __future__ import annotations

import statistics
from typing import List, Optional

WINDOW, SETUP = "segment", "profile"


def _done() -> list:
    try:
        from repro import tracing
    except ImportError:
        return []
    return tracing.spans()


def spans(root: str, clean: bool = True, done: Optional[list] = None
          ) -> list:
    """Completed spans under an outermost span named ``root``; with
    ``clean``, only those that ended without an exception."""
    done = _done() if done is None else done
    return [s for s in done if s.root == root
            and (s.error is None or not clean)]


def _compile_s(counts) -> float:
    return sum((counts or {}).get(k, 0.0) for k in
               ("compile.trace_s", "compile.load_s", "compile.backend_s"))


def checkpoint_phase(name: str, done: Optional[list] = None
                     ) -> Optional[float]:
    """Median over the window's checkpoint saves of the seconds each
    spent in its phase spans named ``name``."""
    win = spans(WINDOW, done=done)
    saves = {s.id for s in win if s.name == "checkpoint"}
    per = {}
    for s in win:
        if s.name == name and s.parent in saves:
            per[s.parent] = per.get(s.parent, 0.0) + s.seconds
    return statistics.median(per.values()) if per else None


def launch_load_s(done: Optional[list] = None) -> Optional[float]:
    """Median over the window's segments of the seconds spent tracing,
    lowering, loading and compiling programs."""
    per = {}
    for s in spans(WINDOW, done=done):
        per[s.root_id] = per.get(s.root_id, 0.0) + _compile_s(s.counters)
    return statistics.median(per.values()) if per else None


def step_feed_s(done: Optional[list] = None) -> Optional[float]:
    """Median over every segment's steps after its first of the host
    time from one step's sync to the next dispatch's return: the
    ``step.data``, ``step.place`` and ``step.dispatch`` spans of a step."""
    by_seg = {}
    for s in spans(WINDOW, clean=False, done=done):
        if s.name in ("step.data", "step.place", "step.dispatch"):
            by_seg.setdefault(s.root_id, {}).setdefault(s.name, []).append(s)
    feeds: List[float] = []
    for parts in by_seg.values():
        steps = zip(*(sorted(parts.get(n, []), key=lambda s: s.t0)
                      for n in ("step.data", "step.place", "step.dispatch")))
        for i, step in enumerate(steps):
            if i and all(s.error is None for s in step):
                feeds.append(sum(s.seconds for s in step))
    return statistics.median(feeds) if feeds else None


def window_compiles(done: Optional[list] = None) -> Optional[int]:
    """Programs compiled in the window: asked of the persistent cache
    and not found there, under every segment."""
    win = spans(WINDOW, done=done)
    if not win:
        return None
    n = 0
    for s in win:
        c = s.counters or {}
        n += c.get("compile.requests", 0) - c.get("compile.cache_hits", 0)
    return n


def trial_compile_s(done: Optional[list] = None) -> Optional[float]:
    """Seconds of the Trial Runner's ``trial.compile`` spans in set-up,
    refused (out-of-memory) compiles included."""
    times = [s.seconds for s in spans(SETUP, clean=False, done=done)
             if s.name == "trial.compile"]
    return sum(times) if times else None
