"""One run of one cell: set-up (process start, the Trial Runner, loading
the step programs the window will launch), the measured window
(``SaturnSession.run(backend="local")`` until the deadline), then the
comparison with the plain reference, and one result line."""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

from . import backlog, correct, spec

RUN_GRACE_S = 120.0      # how long the window's threads get to stop
# JAX's persistent compilation cache: one fixed directory in the checkout
# that nothing else writes to
CACHE_DIR = ".chipbench_cache"


def process_start_time() -> float:
    """This process's start on the ``time.time()`` clock (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            boot = next(int(line.split()[1]) for line in f
                        if line.startswith("btime"))
        return boot + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration, IndexError):
        return time.time()


@dataclasses.dataclass
class Segment:
    """One launch in the window, as it stood at the deadline."""
    job: str
    technique: str
    n_gpus: int
    devices: tuple
    start_step: int
    steps: int                       # steps retired by the deadline
    t0: float                        # launch, window clock (s)
    t1: float                        # end, or the deadline
    mean_step_s: Optional[float]     # mean step after the first
    error: Optional[str]             # failed before the deadline


@dataclasses.dataclass
class Record:
    """What one run measured; metric readers take their number from it."""
    cell: spec.Cell
    jobs: Dict[str, backlog.JobSpec]
    setup_s: float
    profile_s: float
    window_s: float
    tokens: int
    segments: List[Segment]
    plan_s: List[float]
    spans: list                      # Span, window clock
    profiles: object                 # the Trial Runner's profiles
    device_kind: str
    trace: Optional[object] = None   # trace.Summary with --trace 1


def model_config(cfg: dict):
    from repro.models.config import ModelConfig
    names = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cfg.items() if k in names}
    kw["block_pattern"] = tuple(kw["block_pattern"])
    return ModelConfig(**kw)


def warm(sess, cfg, jobs, capture) -> int:
    """Compile (the first time in a checkout) or load every step program
    the window can launch: each feasible (technique, chips) of the
    profiles, for each distinct optimizer setting, with the capture's
    readers for the same shapes.  Returns the number of programs."""
    import jax
    import numpy as np

    from repro.core.perfmodel import iter_job_profiles
    from repro.parallelism.build import BuiltJob

    choices = sorted({(tech, g) for j in jobs
                      for tech, g, p in iter_job_profiles(sess.profiles,
                                                          j.name)
                      if p.feasible})
    opt_cfgs = sorted({j.opt_cfg for j in jobs}, key=repr)
    shapes = sorted({(j.batch_size, j.seq_len) for j in jobs})
    n = 0
    for tech, g in choices:
        plan = sess.library.get(tech).plan(cfg, g)
        devices = jax.devices()[:g]
        first = BuiltJob(cfg, plan, opt_cfgs[0], devices=devices)
        params, opt = first.init(jax.random.PRNGKey(0))
        for b, s in shapes:
            batch = first.place_batch({"tokens": np.zeros((b, s), np.int32)})
            for oc in opt_cfgs:
                built = BuiltJob(cfg, plan, oc, devices=devices)
                built.step.lower(params, opt, batch).compile()
                n += 1
        capture.warm(params, opt)
        del params, opt, batch
    gc.collect()
    return n


def _segments(inst, t_w0: float, t_w1: float) -> List[Segment]:
    out = []
    for ln in inst.launches:
        w = ln.worker
        done = w.done.is_set() and w.finish_clock is not None
        end = ln.clock0 + w.finish_clock if done else t_w1
        err = w.error
        out.append(Segment(
            job=ln.job, technique=ln.technique, n_gpus=ln.n_gpus,
            devices=tuple(ln.devices), start_step=int(w.start_step),
            steps=int(w.steps_done), t0=ln.t0 - t_w0,
            t1=min(end, t_w1) - t_w0, mean_step_s=w.measured_step_s,
            error=(None if err is None else f"{type(err).__name__}: {err}")))
    return out


def _stop_trace() -> None:
    import jax
    t0 = time.monotonic()
    jax.profiler.stop_trace()
    print(f"trace written in {time.monotonic() - t0:.1f} s",
          file=sys.stderr, flush=True)


def _trace_switch(inst, deadline: float, trace_dir: str):
    """Trace the window's first job switch, anchored on steps: from the
    last ``TraceMarks.STEPS`` steps of the first job launched to the
    first ``TraceMarks.STEPS`` steps of the next (or the deadline).
    Host spans and device ops only (no Python tracer).  Returns the
    thread that writes the trace while the window runs on."""
    import jax
    marks = inst.marks
    if not marks.start.wait(max(0.0, deadline - time.monotonic())):
        return None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with inst.span("window"):
        marks.stop.wait(max(0.0, deadline - time.monotonic()))
    stopper = threading.Thread(target=_stop_trace, name="chipbench-trace")
    stopper.start()
    return stopper


def run_window(sess, inst, seconds: float, ckpt_dir: str,
               session_opts: dict, trace_dir: Optional[str]):
    """Drive ``sess.run(backend="local")`` for ``seconds``.  Returns the
    window's start and end (``time.monotonic()``), the segments as they
    stood at the deadline, and the error the run raised, if any other
    than the window closing."""
    from .window import WindowClosed
    outcome = {}

    def target():
        try:
            outcome["result"] = sess.run(backend="local", ckpt_dir=ckpt_dir,
                                         **session_opts)
        except WindowClosed:
            pass
        except BaseException as e:  # reported in the result line
            outcome["error"] = f"{type(e).__name__}: {e}"

    thread = threading.Thread(target=target, name="chipbench-window")
    t_w0 = time.monotonic()
    deadline = t_w0 + seconds
    thread.start()
    stopper = _trace_switch(inst, deadline, trace_dir) if trace_dir \
        else None
    time.sleep(max(0.0, deadline - time.monotonic()))
    t_w1 = time.monotonic()
    segments = _segments(inst, t_w0, t_w1)
    inst.closed.set()
    if stopper is not None:
        stopper.join()
    thread.join(RUN_GRACE_S)
    for ln in inst.launches:
        ln.worker.join(max(1.0, RUN_GRACE_S - (time.monotonic() - t_w1)))
    stuck = [ln.job for ln in inst.launches if ln.worker.is_alive()]
    if thread.is_alive() or stuck:
        outcome["error"] = (f"the window's threads did not stop within "
                            f"{RUN_GRACE_S:.0f} s: run "
                            f"{'alive' if thread.is_alive() else 'ended'}, "
                            f"workers {stuck}")
    for ln in inst.launches:      # drop the tracebacks that hold state
        if isinstance(ln.worker.error, WindowClosed):
            ln.worker.error = None
    return t_w0, t_w1, segments, outcome.get("error")


def report_window(segments: List[Segment], spans) -> None:
    """Each segment and the host spans of the window, on standard error,
    so that a run that reads far off can be taken apart."""
    for s in segments:
        step = f"{s.mean_step_s:.4f} s" if s.mean_step_s else "-"
        print(f"segment {s.job} {s.technique} x{s.n_gpus}: steps "
              f"{s.start_step}+{s.steps}, {s.t0:.3f}-{s.t1:.3f} s, mean step "
              f"{step}{'' if s.error is None else ', ' + s.error}",
              file=sys.stderr)
    for s in spans:
        if s.name not in ("step", "capture"):
            print(f"span {s.name} {s.thread}: {s.t0:.3f}-{s.t1:.3f} s",
                  file=sys.stderr)
    steps = [s for s in spans if s.name == "step"]
    if steps:
        print(f"spans step: {len(steps)}, "
              f"{sum(s.t1 - s.t0 for s in steps):.3f} s", file=sys.stderr)
    sys.stderr.flush()


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> dict:
    import jax

    from repro.core.api import SaturnSession
    from repro.core.job import ClusterSpec

    from . import reference
    from . import trace as trace_mod
    from .window import Capture, CompileCounter, Instruments, TraceMarks

    counter = CompileCounter()
    traffic, conf = cell.traffic, cell.config
    session = traffic["session"]
    cfg = model_config(conf)
    specs = backlog.expand(traffic, seed)
    jobs = backlog.saturn_jobs(specs, cfg)
    sess = SaturnSession(ClusterSpec(
        nodes=1, gpus_per_node=cell.chips,
        restart_cost_s=float(session["restart_cost_s"])))
    sess.submit(jobs)
    t0 = time.monotonic()
    sess.profile(**session["profile"])
    profile_s = time.monotonic() - t0
    capture = Capture(reference.family(conf["family"]).spec(conf),
                      conf["optimizer"]["b1"])
    programs = warm(sess, cfg, jobs, capture)
    setup_s = time.time() - t_start
    print(f"set-up {setup_s:.3f} s: profile {profile_s:.3f} s, "
          f"{programs} step programs loaded", file=sys.stderr, flush=True)

    inst = Instruments(capture, annotate=trace, marks=TraceMarks()
                       if trace else None)
    scratch = tempfile.mkdtemp(prefix="chipbench-")
    trace_dir = os.path.join(scratch, "trace") if trace else None
    inst.install()
    try:
        r0, h0 = counter.snapshot()
        t_w0, t_w1, segments, run_error = run_window(
            sess, inst, seconds, os.path.join(scratch, "ckpt"),
            {"introspect_every_s": session["introspect_every_s"]},
            trace_dir)
        r1, h1 = counter.snapshot()
    finally:
        inst.uninstall()
    compiles = (r1 - r0) - (h1 - h0)
    print(f"compiles in the window: {compiles} ({r1 - r0} programs "
          f"requested, {h1 - h0} loaded from the cache)", file=sys.stderr,
          flush=True)
    used = jax.devices()[:cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    summary = None
    if trace_dir:
        t0 = time.monotonic()
        summary = trace_mod.reduce(trace_dir, "window",
                                   [d.id for d in used])
        print(f"trace reduced in {time.monotonic() - t0:.1f} s",
              file=sys.stderr, flush=True)
    spans = [dataclasses.replace(s, t0=s.t0 - t_w0, t1=s.t1 - t_w0)
             for s in inst.spans]
    report_window(segments, spans)
    by_name = {s.name: s for s in specs}
    tokens = sum(s.steps * by_name[s.job].batch * by_name[s.job].seq
                 for s in segments)
    rec = Record(
        cell=cell, jobs=by_name, setup_s=setup_s, profile_s=profile_s,
        window_s=t_w1 - t_w0, tokens=tokens, segments=segments,
        plan_s=list(inst.plan_s), spans=spans,
        profiles=sess.profiles,
        device_kind=used[0].device_kind, trace=summary)

    # ---- correct: the first steps of sampled jobs against the reference
    workers = {ln.job: ln.worker for ln in inst.launches
               if ln.worker.start_step == 0}
    del sess, inst
    gc.collect()
    checks = correct.check_run(rec, workers, capture, seed, run_error)
    shutil.rmtree(scratch, ignore_errors=True)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in getattr(cell, kind):
        v = m.read(rec)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": checks.ok, "attempted": len(segments),
           "failed": sum(1 for s in segments if s.error),
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = checks.table()
    return out


def main(argv=None) -> int:
    t_start = process_start_time()
    ap = argparse.ArgumentParser(description="Run one cell of the "
                                 "benchmark on this machine's chips.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(spec.ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chipbench: the system under test is not here ({src})",
              file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload)
    # JAX's persistent compilation cache: one fixed directory inside
    # the checkout, whatever the environment says
    cache = os.path.join(spec.ROOT, CACHE_DIR)
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, src)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    for line in correct.check_lines(out["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
