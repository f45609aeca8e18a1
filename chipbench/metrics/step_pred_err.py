"""Median over the window's segments of |predicted - measured| /
measured step time: the Trial Runner's profile of the chosen (technique,
chips) against the segment's mean step after its first."""
import statistics


def read(run):
    from repro.core.perfmodel import lookup_profile
    errs = []
    for s in run.segments:
        if not s.mean_step_s:
            continue
        p = lookup_profile(run.profiles, s.job, s.technique, s.n_gpus)
        if p is not None and p.feasible:
            errs.append(abs(p.step_time_s - s.mean_step_s) / s.mean_step_s)
    return statistics.median(errs) if errs else None
