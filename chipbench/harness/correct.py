"""The comparison that decides ``correct``.

Training is compared step for step with the plain reference (see
:mod:`.reference`): for a sample of the jobs that started from step 0 in
the window, drawn from the seed, what the window's own steps gave (the
tokens each of the first three steps received, their losses, the
per-leaf norm of the first gradient as AdamW took it, and the per-leaf
norm of the weights' change after three steps) against the reference's.
Norms are compared leaf by leaf: the gap between the program's norm and
the reference's, over the larger of the reference's norm of that leaf
and of the median leaf; the worst leaf and the median leaf give a
number each.  Leaves whose reference gradient is under a thousandth of
the median leaf's move by round-off alone and are left out of the
change.  The schedule is checked exactly: no launch on a (technique,
chips) its profile marked infeasible, no chip given to two launches at
once, and every resumed segment starts where its job's last one ended.
A cell's traffic mix names, under ``correct.limits``, the numbers it is
held to and their limits.
"""
from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Optional

import numpy as np

from .reference import Readings, first_steps

STEPS = 3
ROUND_OFF = 1e-3       # leaves under this share of the median gradient
GAPS = ("batch_mismatch", "loss1_gap", "loss_gap", "grad_gap",
        "grad_gap_median", "change_gap", "change_gap_median")


@dataclasses.dataclass
class Checks:
    rows: List[tuple] = dataclasses.field(default_factory=list)
    notes: List[str] = dataclasses.field(default_factory=list)

    def add(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit)))

    @property
    def ok(self) -> bool:
        return not self.notes and all(v <= lim for _, v, lim in self.rows)

    def table(self) -> Dict[str, dict]:
        out = {n: {"value": v, "limit": lim} for n, v, lim in self.rows}
        for i, note in enumerate(self.notes):
            out[f"fault{i}"] = {"value": note, "limit": "none"}
        return out


def check_lines(table: Dict[str, dict]) -> List[str]:
    return [f"check {name}: {r['value']} (limit {r['limit']})"
            for name, r in table.items()]


def _leaf_gaps(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per leaf: |program norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    return np.abs(prog - ref) / np.maximum(ref, float(np.median(ref)))


def gaps(prog: Readings, ref: Readings) -> Dict[str, float]:
    """The numbers one job gives; a cell's ``correct.limits`` picks the
    ones it holds to a limit."""
    n = min(len(prog.losses), len(ref.losses))
    keep = ref.grad >= ROUND_OFF * float(np.median(ref.grad))
    grad = _leaf_gaps(prog.grad, ref.grad)
    change = _leaf_gaps(prog.change[keep], ref.change[keep])
    return {
        "batch_mismatch": float(sum(
            a.shape != b.shape or not np.array_equal(a, b)
            for a, b in zip(prog.tokens, ref.tokens))
            + abs(len(prog.tokens) - len(ref.tokens))),
        "loss1_gap": abs(prog.losses[0] - ref.losses[0]),
        "loss_gap": max(abs(a - b) for a, b in zip(prog.losses[:n],
                                                   ref.losses[:n])),
        "grad_gap": float(np.max(grad)),
        "grad_gap_median": float(np.median(grad)),
        "change_gap": float(np.max(change)),
        "change_gap_median": float(np.median(change)),
    }


def worst_leaves(prog: Readings, ref: Readings, k: int = 5) -> dict:
    """The leaves that set ``grad_gap`` and ``change_gap``, worst first:
    ``(path, program norm, reference norm, gap)``."""
    out = {}
    for name, a, b in (("grad", prog.grad, ref.grad),
                       ("change", prog.change, ref.change)):
        med = float(np.median(b))
        gap = np.abs(a - b) / np.maximum(b, med)
        out[name] = [(ref.paths[i], float(a[i]), float(b[i]), float(gap[i]))
                     for i in np.argsort(-gap)[:k]]
    return out


def schedule_faults(rec) -> Dict[str, int]:
    """Launches on an infeasible choice, pairs of launches that held a
    chip at once, and resumed segments that do not start where their
    job's previous segment ended."""
    from repro.core.perfmodel import lookup_profile
    infeasible = 0
    for s in rec.segments:
        p = lookup_profile(rec.profiles, s.job, s.technique, s.n_gpus)
        if p is None or not p.feasible:
            infeasible += 1
    shared = 0
    segs = rec.segments
    for i, a in enumerate(segs):
        for b in segs[i + 1:]:
            if set(a.devices) & set(b.devices) and a.t0 < b.t1 and \
                    b.t0 < a.t1:
                shared += 1
    broken, last = 0, {}
    for s in sorted(segs, key=lambda s: s.t0):
        if s.start_step and last.get(s.job) != s.start_step:
            broken += 1
        last[s.job] = s.start_step + s.steps
    return {"infeasible_launches": infeasible, "shared_chips": shared,
            "broken_resumes": broken}


def program_readings(worker, capture) -> Optional[Readings]:
    """What the window's own run of a job gave, if it got far enough."""
    if worker not in capture.grad or worker not in capture.change or \
            len(worker.losses) < STEPS:
        return None
    return Readings(capture.tokens[worker],
                    [v for _, v in worker.losses[:STEPS]],
                    capture.grad[worker], capture.change[worker],
                    list(capture.paths))


def check_run(rec, workers: Dict[str, object], capture, seed: int,
              run_error: Optional[str]) -> Checks:
    checks = Checks()
    if run_error:
        checks.notes.append(run_error)
    for s in rec.segments:
        if s.error:
            checks.notes.append(f"{s.job}: {s.error}")
    for name, n in schedule_faults(rec).items():
        checks.add(name, n, 0)
    settings = rec.cell.traffic["correct"]
    limits = settings["limits"]
    ready = {j: program_readings(w, capture) for j, w in workers.items()}
    ready = sorted(j for j, r in ready.items() if r is not None)
    if not ready:
        checks.notes.append("no job reached step 4 in the window: "
                            "nothing to compare")
        return checks
    sample = random.Random(seed).sample(
        ready, min(int(settings["jobs"]), len(ready)))
    worst = {k: 0.0 for k in limits}
    for k in limits:
        if k not in GAPS:
            checks.notes.append(f"no such number: {k}")
    for name in sample:
        job = rec.jobs[name]
        prog = program_readings(workers[name], capture)
        ref = first_steps(rec.cell.config, job.seed, batch=job.batch,
                          seq=job.seq, lr=job.lr, total_steps=job.steps,
                          steps=STEPS)
        if prog.paths != ref.paths:
            checks.notes.append(f"{name}: parameter trees differ")
            continue
        for k, v in gaps(prog, ref).items():
            if k in worst:
                worst[k] = max(worst[k], v)
    for k, lim in limits.items():
        checks.add(k, worst[k], lim)
    return checks
