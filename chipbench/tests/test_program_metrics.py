"""The per-layer metrics read from the system's own spans and counters:
each reader on a tracer filled by hand (medians, spans ended by an
exception left out, spans outside a ``segment`` or ``profile`` left out,
None where nothing is found), and every one of them on a tiny run of the
whole cell on the CPU."""
import json
import os
import time

import pytest

import tiny
from harness import bench, program, spec
from repro import tracing

NEW = ("ckpt_fetch_s", "ckpt_hash_s", "ckpt_write_s", "ckpt_fsync_s",
       "launch_load_s", "step_feed_s", "window_compiles", "trial_compile_s")
SEED = 2 ** 31 + 7


@pytest.fixture
def tracer(monkeypatch):
    """A fresh tracer in place of the process's own."""
    tr = tracing.Tracer()
    for name in ("span", "count", "spans", "totals", "counters"):
        monkeypatch.setattr(tracing, name, getattr(tr, name))
    return tr


def _read(name):
    return spec._reader(name)(None)


class _Clock:
    """Stands in for ``time.monotonic``: each span lasts what it is told."""

    def __init__(self, monkeypatch):
        self.t = 100.0
        monkeypatch.setattr(tracing, "_now", lambda: self.t)

    def span(self, tr, name, dt, **kw):
        sp = tr.span(name, **kw)
        sp.__enter__()
        self.t += dt
        return sp

    @staticmethod
    def end(sp, error=None):
        sp.__exit__(error, error() if error else None, None)


def _segment(tr, clock, saves, steps, compile_s, compiles=0, cut=False):
    """One worker segment: ``steps`` (data, place, dispatch) triples,
    the compile counters on its init, then a save with the given phase
    seconds; a cut segment ends in its last dispatch instead."""
    seg = clock.span(tr, "segment", 0.0)
    init = clock.span(tr, "init", 0.5)
    tr.count("compile.trace_s", compile_s)
    tr.count("compile.requests", compiles + 1)
    tr.count("compile.cache_hits", 1)
    clock.end(init)
    for i, (d, p, x) in enumerate(steps):
        last = cut and i == len(steps) - 1
        for name, dt in (("step.data", d), ("step.place", p),
                         ("step.dispatch", x)):
            sp = clock.span(tr, name, dt)
            if last and name == "step.dispatch":
                clock.end(sp, RuntimeError)
                clock.end(seg, RuntimeError)
                return
            clock.end(sp)
        clock.end(clock.span(tr, "step.sync", 0.3))
    ck = clock.span(tr, "checkpoint", 0.0)
    for name, dt in saves.items():
        clock.end(clock.span(tr, name, dt))
    clock.end(ck)
    clock.end(seg)


def test_readers_on_a_tracer_filled_by_hand(tracer, monkeypatch):
    clock = _Clock(monkeypatch)
    tr = tracer
    # set-up: two trials, one compile refused
    prof = clock.span(tr, "profile", 0.0)
    for dt, err in ((4.0, None), (6.0, RuntimeError)):
        trial = clock.span(tr, "trial", 0.0)
        c = clock.span(tr, "trial.compile", dt)
        clock.end(c, err)
        clock.end(trial)
    clock.end(prof)
    # outside any segment or profile: a warm-up load and a stray save
    warm = clock.span(tr, "init", 9.0)
    tr.count("compile.trace_s", 9.0)
    tr.count("compile.requests", 5)
    clock.end(warm)
    stray = clock.span(tr, "checkpoint", 0.0)
    clock.end(clock.span(tr, "checkpoint.hash", 50.0))
    clock.end(stray)
    # the window: three segments, the last one cut at the deadline
    phases = {"checkpoint.fetch": 1.0, "checkpoint.hash": 4.0,
              "checkpoint.write": 2.0, "checkpoint.fsync": 1.5,
              "checkpoint.rotate": 0.01}
    _segment(tr, clock, phases, [(0.1, 0.01, 0.9), (0.02, 0.001, 0.001),
                                 (0.03, 0.001, 0.001)], compile_s=2.0)
    _segment(tr, clock, {**phases, "checkpoint.hash": 6.0},
             [(0.1, 0.01, 0.9), (0.01, 0.001, 0.001)], compile_s=3.0,
             compiles=1)
    _segment(tr, clock, {}, [(0.1, 0.01, 0.9), (0.04, 0.001, 0.001),
                             (0.05, 0.0, 5.0)], compile_s=4.0, cut=True)
    assert _read("ckpt_fetch_s") == pytest.approx(1.0)
    assert _read("ckpt_hash_s") == pytest.approx(5.0)   # 4 and 6, not 50
    assert _read("ckpt_write_s") == pytest.approx(2.0)
    assert _read("ckpt_fsync_s") == pytest.approx(1.5)
    assert _read("launch_load_s") == pytest.approx(3.0)  # not the warm 9
    # steps after each segment's first; the cut dispatch left out
    assert _read("step_feed_s") == pytest.approx(0.027)
    assert _read("window_compiles") == 1
    assert _read("trial_compile_s") == pytest.approx(10.0)


def test_readers_find_nothing_and_say_so(tracer):
    with tracer.span("profile"):
        pass
    with tracer.span("init"):
        tracer.count("compile.requests", 3)
    for name in NEW:
        assert _read(name) is None, name
    with tracer.span("segment"):
        with tracer.span("init"):
            tracer.count("compile.requests", 1)
            tracer.count("compile.cache_hits", 1)
    assert _read("window_compiles") == 0
    assert _read("launch_load_s") == 0.0


def test_a_system_without_the_tracer_reads_nothing(monkeypatch):
    import sys

    import repro
    with tracing.span("segment"):
        pass
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert program.spans(program.WINDOW) == []
    for name in NEW:
        assert _read(name) is None, name


def test_every_new_metric_has_a_reader_and_its_cells():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench_json = json.load(f)
    entries = {m["name"]: m for m in bench_json["per_layer"]}
    cells = {w["name"] for w in bench_json["workloads"]}
    for name in NEW:
        m = entries[name]
        assert os.path.exists(os.path.join(spec.BENCH_DIR, "metrics",
                                           name + ".py"))
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert m["source"] in ("program_span", "program_counter")


def test_every_new_metric_reads_a_tiny_run_of_the_cell(tracer):
    cell = tiny.cell("xlstm", metrics=("sweep_tokens_per_s",) + NEW)
    out = bench.run_cell(cell, SEED, 6.0, False, time.time())
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert set(NEW) <= set(got), sorted(got)
    for name in NEW:
        assert got[name]["value"] >= 0, name
    assert got["window_compiles"]["value"] == 0
    assert got["trial_compile_s"]["value"] > 0
    assert got["ckpt_hash_s"]["value"] > 0
