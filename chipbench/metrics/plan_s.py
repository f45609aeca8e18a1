"""Seconds the policy spent in ``plan`` / ``plan_incremental`` inside the
window, summed over calls."""


def read(run):
    return sum(run.plan_s) if run.plan_s else None
