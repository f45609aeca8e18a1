"""The in-process tracer: spans nest and link per thread and across the
launch -> segment hand-off, counters land on the innermost span, JAX's
compile events are attributed where they happen, and a tiny local run
yields the segment's span tree and sits in a profiler trace."""
import dataclasses
import glob
import threading

import numpy as np
import pytest

from repro import tracing
from repro.configs import get_config
from repro.core.baselines import CurrentPractice
from repro.core.executor import simulate
from repro.core.job import ClusterSpec, Job
from repro.core.local_backend import LocalJaxBackend
from repro.core.profiler import Profile

MICRO = dataclasses.replace(get_config("xlstm-125m").reduced(), d_model=64,
                            num_heads=2, num_kv_heads=2, head_dim=32,
                            name="xlstm-micro")
CLUSTER = ClusterSpec(nodes=1, gpus_per_node=1, restart_cost_s=0.5)


def _by_id(tr):
    return {s.id: s for s in tr.spans()}


def test_spans_nest_per_thread_and_link_across_the_hand_off():
    tr = tracing.Tracer()
    seen = {}

    def worker(launch):
        with tr.span("segment", parent=launch, **launch.attrs) as seg:
            with tr.span("init") as init:
                tr.count("c", 2)
        seen.update(seg=seg, init=init)

    with tr.span("replan") as rp:
        pass
    with tr.span("launch", job="j", token=7) as ln:
        with tr.span("inner") as inner:
            th = threading.Thread(target=worker, args=(ln,), name="w")
            th.start()
            th.join()
    seg, init = seen["seg"], seen["init"]
    assert rp.parent is None and rp.root == "replan"
    assert inner.parent == ln.id and inner.root == "launch"
    assert seg.parent == ln.id                # explicit, across threads
    assert seg.attrs == {"job": "j", "token": 7}
    assert seg.thread == "w" and ln.thread != "w"
    assert seg.root == "segment" and seg.root_id == seg.id
    assert init.parent == seg.id and init.root_id == seg.id
    assert init.counters == {"c": 2} and seg.counters is None
    assert seg.tree_counts == {"c": 2}
    assert seg.tree_spans == {"init": [1, init.seconds]}
    assert ln.t0 <= inner.t0 <= seg.t0 <= init.t0 <= init.t1 <= seg.t1
    assert set(_by_id(tr)) == {s.id for s in (rp, ln, inner, seg, init)}
    assert tr.totals()["init"] == (1, init.seconds)
    assert tr.counters() == {"c": 2}
    assert tr._local.stack == []


def test_a_span_ended_by_an_exception_is_marked():
    tr = tracing.Tracer()
    with pytest.raises(KeyError):
        with tr.span("outer") as outer:
            with tr.span("ok") as ok:
                pass
            with tr.span("bad") as bad:
                raise KeyError("x")
    assert ok.error is None
    assert bad.error == "KeyError" and outer.error == "KeyError"
    assert bad.t1 is not None and tr._local.stack == []


def test_the_buffer_drops_the_oldest_and_counts_the_drops():
    tr = tracing.Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]
    assert tr.dropped == 6
    assert len(tr.totals()) == 10             # totals drop nothing


def test_a_fresh_jit_counts_its_compile_on_its_own_span_only():
    import jax
    import jax.numpy as jnp

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    x = jnp.arange(8.0)
    jax.block_until_ready(x)
    salt = float(np.random.default_rng().integers(1 << 30))
    with tracing.span("test.outer") as outer:
        with tracing.span("test.compile") as inner:
            jax.block_until_ready(jax.jit(lambda v: v * salt + 1.0)(x))
        with tracing.span("test.after") as after:
            pass
    c = inner.counters
    assert c["compile.requests"] >= 1
    assert c.get("compile.cache_hits", 0) <= c["compile.requests"]
    assert c["compile.trace_s"] > 0
    assert c["compile.backend_s"] + c.get("compile.load_s", 0) > 0
    assert outer.counters is None and after.counters is None
    assert outer.tree_counts == c               # the root sees it too
    assert tracing.compile_seconds(c) <= inner.seconds


def _tiny_run(tmp_path, name, steps=4):
    jobs = [Job(name, MICRO, 2, 32, total_steps=steps, lr=1e-3, seed=0)]
    profiles = {(name, "ddp", 1): Profile(name, "ddp", 1, 0.01, 1e9, True,
                                          "t")}
    be = LocalJaxBackend(ckpt_dir=str(tmp_path))
    return simulate(jobs, CurrentPractice(), profiles, CLUSTER,
                    exec_backend=be)


def _segment_tree(name):
    done = tracing.spans()
    launch = [s for s in done if s.name == "launch"
              and s.attrs.get("job") == name]
    assert len(launch) == 1
    seg, = [s for s in done if s.name == "segment"
            and s.parent == launch[0].id]
    below = [s for s in done if s.root_id == seg.id and s is not seg]
    return launch[0], seg, below


def test_a_local_run_yields_the_segment_tree(tmp_path):
    res = _tiny_run(tmp_path, "trace-j0", steps=4)
    launch, seg, below = _segment_tree("trace-j0")
    assert seg.error is None and seg.thread != launch.thread
    assert seg.attrs["job"] == "trace-j0" and seg.attrs["chips"] == 1
    by = {}
    for s in below:
        by.setdefault(s.name, []).append(s)
    top = [s.name for s in sorted(below, key=lambda s: s.t0)
           if s.parent == seg.id]
    assert top[:3] == ["build", "init", "restore"]
    assert top[3:-1] == ["step.data", "step.place", "step.dispatch",
                         "step.sync"] * 4 + ["step.data"]
    assert top[-1] == "checkpoint"
    ckpt, = by["checkpoint"]
    phases = [s for s in below if s.parent == ckpt.id]
    assert {s.name for s in phases} == {
        "checkpoint.fetch", "checkpoint.hash", "checkpoint.write",
        "checkpoint.fsync", "checkpoint.rotate"}
    assert sum(s.seconds for s in phases) <= ckpt.seconds
    with np.load(tmp_path / "trace-j0.npz") as z:
        saved = sum(z[k].nbytes for k in z.files
                    if k != "__saturn_meta__")
    assert ckpt.counters["checkpoint.bytes"] == saved
    # the compile work sits on init and the first dispatch
    dispatch = by["step.dispatch"]
    assert by["init"][0].counters["compile.trace_s"] > 0
    assert dispatch[0].counters["compile.trace_s"] > 0
    assert all(d.counters is None for d in dispatch[1:])
    # the segment's stats read the same spans
    st = res.stats["trace-j0"]["segments"][0]
    assert st["compile_s"] == pytest.approx(
        tracing.compile_seconds(seg.tree_counts))
    assert st["spans"]["step.dispatch"]["n"] == 4
    assert st["spans"]["checkpoint"]["s"] == pytest.approx(ckpt.seconds)
    places = sorted(by["step.place"], key=lambda s: s.t0)
    syncs = sorted(by["step.sync"], key=lambda s: s.t0)
    assert st["first_step_s"] == pytest.approx(syncs[0].t1 - places[0].t0)
    assert st["measured_step_s"] == pytest.approx(np.mean(
        [b.t1 - a.t0 for a, b in zip(places[1:], syncs[1:])]))


def test_a_profiler_trace_holds_the_programs_spans(tmp_path):
    import jax
    from jax.profiler import ProfileData
    trace_dir = tmp_path / "trace"
    jax.profiler.start_trace(str(trace_dir))
    try:
        _tiny_run(tmp_path / "ckpt", "trace-j1", steps=2)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith(tracing.PREFIX)}
    assert {"saturn.launch", "saturn.segment", "saturn.init",
            "saturn.step.dispatch", "saturn.step.sync", "saturn.checkpoint",
            "saturn.checkpoint.hash"} <= names
