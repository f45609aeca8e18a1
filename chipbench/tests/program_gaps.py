#!/usr/bin/env python3
"""One traced run of a cell, read through the system's own spans: each
idle gap of the device in the traced switch put down to the innermost
``saturn.*`` span that covers most of it, the program's spans set beside
the benchmark's ``chipbench.*`` spans around the same calls, the
program's counters per segment and in set-up, and what one span costs
with and without a profiler session.

    python3 chipbench/tests/program_gaps.py --workload <cell> --seed <n> \
        --seconds <s> [--keep <dir>]     # on a TPU

The result line of the run is the last line of standard output, as from
``run.py --trace 1``; the readings go to standard error.  ``--keep``
copies the trace there.
"""
import argparse
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import bench, trace  # noqa: E402

SATURN, BENCH = "saturn.", "chipbench."
GAPS = 12
# (program span, benchmark span) around the same call
PAIRS = (("checkpoint", "checkpoint"), ("init", "init"),
         ("restore", "restore"), ("launch", "launch"), ("build", "build"))


def _say(*a):
    print(*a, file=sys.stderr)


def _host_events(data):
    """Every host event: (name, start, end, thread line)."""
    out = []
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for name, a, b in trace._events(line):
                out.append((name, a, b, line.name))
    return out


def _between(events, lo, hi, t0):
    """The host events of at least a millisecond that overlap
    ``[lo, hi]`` without covering all of it, longest first."""
    hits = sorted((e for e in events if _cover((lo, hi), e[1], e[2]) > 0
                   and e[2] - e[1] >= 1e6 and not e[1] <= lo < hi <= e[2]),
                  key=lambda e: e[1] - e[2])
    return "; ".join(f"{n} [{ln}] +{(a - t0) / 1e9:.4f}-{(b - t0) / 1e9:.4f}"
                     for n, a, b, ln in hits[:16])


def _device_busy(data, lo, hi):
    for plane in data.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get(trace.OPS_LINE) or lines.get(trace.MODULES_LINE)
            return trace.union([(max(a, lo), min(b, hi))
                                for _, a, b in trace._events(ops)
                                if min(b, hi) > max(a, lo)])
    raise ValueError("no device plane in the trace")


def _cover(gap, a, b):
    return max(0, min(b, gap[1]) - max(a, gap[0]))


def label(gap, spans):
    """The innermost span covering most of ``gap``: the most covered,
    and of those the shortest."""
    best = max(spans, default=None,
               key=lambda s: (_cover(gap, s[1], s[2]), -(s[2] - s[1])))
    if best is None or _cover(gap, best[1], best[2]) == 0:
        return "unattributed"
    return best[0]


def read_trace(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    every = _host_events(data)
    events = [e[:3] for e in every if e[0].startswith((SATURN, BENCH))]
    win = [(a, b) for n, a, b in events if n == BENCH + "window"]
    if not win:
        raise ValueError("no chipbench.window span in the trace")
    lo, hi = win[0]
    ours = [(n[len(SATURN):], a, b) for n, a, b in events
            if n.startswith(SATURN)]
    theirs = [(n[len(BENCH):], a, b) for n, a, b in events
              if n.startswith(BENCH) and n != BENCH + "window"]
    busy = _device_busy(data, lo, hi)
    idle = sorted(trace.gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
    _say(f"program trace: window {(hi - lo) / 1e9:.3f} s, device busy "
         f"{sum(b - a for a, b in busy) / 1e9:.3f} s, "
         f"{len(ours)} saturn spans")
    for g in idle[:GAPS]:
        parts = sorted(((_cover(g, a, b), n) for n, a, b in ours
                        if _cover(g, a, b) > 0.05 * (g[1] - g[0])),
                       reverse=True)
        _say(f"gap {(g[1] - g[0]) / 1e9:.4f} s at +{(g[0] - lo) / 1e9:.3f}: "
             f"program {label(g, ours)}, benchmark {label(g, theirs)}; "
             + ", ".join(f"{n} {c / 1e9:.4f}" for c, n in parts[:8]))
    for mine, bench_name in PAIRS:
        for n, a, b in theirs:
            if n != bench_name:
                continue
            match = [(x, y) for m, x, y in ours if m == mine
                     and _cover((a, b), x, y) > 0]
            for x, y in match:
                _say(f"pair {mine}: program {(y - x) / 1e9:.4f} s, "
                     f"benchmark {(b - a) / 1e9:.4f} s, "
                     f"differ {((y - x) - (b - a)) / max(b - a, 1):+.4%}; "
                     f"program starts {(a - x) / 1e6:.3f} ms before, "
                     f"ends {(y - b) / 1e6:.3f} ms after")
                if abs((y - x) - (b - a)) > 1e6:
                    _say(f"pair {mine} apart: before "
                         f"{_between(every, x, a, lo)} | after "
                         f"{_between(every, b, y, lo)}")
    firsts = [(a, b) for n, a, b in theirs if n == "first_step"]
    for a, b in firsts:
        disp = [(x, y) for m, x, y in ours if m == "step.dispatch"
                and _cover((a, b), x, y) > 0]
        for x, y in disp:
            _say(f"pair first dispatch: program {(y - x) / 1e9:.4f} s, "
                 f"benchmark first_step {(b - a) / 1e9:.4f} s")


def report_program():
    """The program's own spans and counters, from memory."""
    from repro import tracing
    done = tracing.spans()
    by_id = {s.id: s for s in done}
    segs = [s for s in done if s.name == "segment"]
    for seg in segs:
        below = [s for s in done if s.root_id == seg.id]
        tot = {k: v for k, v in sorted(tracing.span_totals(seg).items())}
        _say(f"program segment {seg.attrs.get('job')} "
             f"{seg.seconds:.3f} s error {seg.error}: "
             + ", ".join(f"{k} {v['n']}x {v['s']:.4f}" for k, v in
                         tot.items()))
        _say(f"program segment {seg.attrs.get('job')} counters "
             f"{seg.tree_counts}")
        for ck in (s for s in below if s.name == "checkpoint"):
            phases = [s for s in below if s.parent == ck.id]
            parts = {}
            for s in phases:
                parts[s.name] = parts.get(s.name, 0.0) + s.seconds
            four = sum(v for k, v in parts.items()
                       if k != "checkpoint.rotate")
            _say(f"program checkpoint {ck.seconds:.4f} s, bytes "
                 f"{(ck.counters or {}).get('checkpoint.bytes')}, four "
                 f"phases {four:.4f} s ({four / ck.seconds:.2%}): "
                 + ", ".join(f"{k} {v:.4f}" for k, v in sorted(
                     parts.items())))
        steps = sorted((s for s in below if s.name.startswith("step.")),
                       key=lambda s: s.t0)
        for name in ("step.data", "step.place", "step.dispatch",
                     "step.sync"):
            ts = [s.seconds for s in steps if s.name == name][1:]
            if ts:
                _say(f"program {seg.attrs.get('job')} {name}: median "
                     f"{statistics.median(ts) * 1e3:.3f} ms, max "
                     f"{max(ts) * 1e3:.3f} ms over {len(ts)}")
    for prof in (s for s in done if s.name == "profile"):
        _say(f"program profile {prof.seconds:.3f} s counters "
             f"{prof.tree_counts}")
        for t in (s for s in done if s.root_id == prof.id
                  and s.name.startswith("trial")):
            parent = by_id.get(t.parent)
            attrs = t.attrs or (parent.attrs if parent else {})
            _say(f"program {t.name} {attrs.get('job')} "
                 f"{attrs.get('technique')} x{attrs.get('chips')}: "
                 f"{t.seconds:.3f} s error {t.error} "
                 f"counters {t.counters}")
    _say(f"program counters {tracing.counters()} dropped "
         f"{tracing.TRACER.dropped}")


def span_cost(n=200_000):
    """Microseconds per empty span, with no profiler session and with
    one, and of a bare annotation for reference."""
    import jax
    from repro import tracing
    tr = tracing.Tracer(capacity=1024)
    out = {}

    def each(fn, k):
        t0 = time.perf_counter()
        for _ in range(k):
            fn()
        return (time.perf_counter() - t0) / k * 1e6

    def one():
        with tr.span("cost"):
            pass

    def bare():
        with jax.profiler.TraceAnnotation("saturn.cost"):
            pass

    out["off_us"] = each(one, n)
    out["annotation_off_us"] = each(bare, n)
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    try:
        out["on_us"] = each(one, n // 10)
        out["annotation_on_us"] = each(bare, n // 10)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(tmp, ignore_errors=True)
    _say("span cost " + ", ".join(f"{k} {v:.3f}" for k, v in out.items()))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args()
    reduce = trace.reduce

    def reduce_and_read(trace_dir, window, device_ids=None):
        path = trace.xplane_file(trace_dir)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            shutil.copy(path, args.keep)
        try:
            read_trace(path)
        except Exception as e:  # the run's own result still stands
            _say(f"program trace unreadable: {type(e).__name__}: {e}")
        return reduce(trace_dir, window, device_ids)

    trace.reduce = reduce_and_read
    rc = bench.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "1"])
    if rc == 0:
        report_program()
        span_cost()
    return rc


if __name__ == "__main__":
    sys.exit(main())
