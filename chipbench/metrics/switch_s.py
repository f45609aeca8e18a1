"""Median over the window's segments (a running one up to the deadline)
of its time on the runtime's clock less its steps after the first times
its mean step: launch, build, initialise or restore, program load and
checkpoint, the time a segment holds its chip without training."""
import statistics


def read(run):
    out = []
    for s in run.segments:
        steady = (s.steps - 1) * s.mean_step_s if s.mean_step_s else 0.0
        out.append(s.t1 - s.t0 - steady)
    return statistics.median(out) if out else None
