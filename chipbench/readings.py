#!/usr/bin/env python3
"""The readings the correctness limits are set from, at a cell's own
size, in one process on the chip (the benchmark's runs do not run this).

    python3 chipbench/readings.py --workload <cell> --seeds 12 \
        --faulty-seeds 3 [--out <file>]

The Trial Runner profiles the cell's backlog as a run's set-up does, and
the job's (technique, chips) is the fastest feasible one, as the window
would launch it.  For each seed, one job of the cell's backlog is
trained for three steps by the system under test (that step program, fed
by its own data pipeline) and by the plain float32 reference, and the
numbers of :mod:`harness.correct` are read: the lower readings.  For the
first ``--faulty-seeds`` seeds the reference is also run as the control
(in bfloat16), and each fault a one-chip training cell can have is
planted: half of the batch left out inside the program's step (the
second half of the rows repeats the first, so the mean is over half;
the tokens are read before, as the window reads them), and one token of
every row altered where the reference's feed produces it.  These are
the upper readings.  A state left unchanged reads 1 on ``change_gap``
by its definition and needs no run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import backlog, correct, spec  # noqa: E402


def program_first_steps(built, capture, cfg, job, fault=None,
                        steps=correct.STEPS):
    """The system under test's first steps of ``job``, read the way the
    window's capture reads them; ``fault="half_batch"`` hands the step a
    batch whose second half repeats the first, after the tokens are
    read."""
    import jax
    import numpy as np

    from repro.data.synthetic import SyntheticLM
    from harness.reference import Readings
    params, opt = built.init(jax.random.PRNGKey(job.seed))
    tokens, losses, grad = [], [], None
    for t, b in enumerate(SyntheticLM(cfg, seed=job.seed).batches(
            job.batch, job.seq, num_batches=steps)):
        if t == 1:
            grad = np.asarray(capture._norms(opt["mu"])) / (1 - capture.b1)
        placed = built.place_batch(b)
        tokens.append(np.asarray(placed["tokens"]))
        if fault == "half_batch":
            rows = np.array(tokens[-1])
            rows[len(rows) // 2:] = rows[: len(rows) // 2]
            placed = built.place_batch({**b, "tokens": rows})
        elif fault is not None:
            raise ValueError(f"unknown fault {fault!r}")
        params, opt, m = built.step(params, opt, placed)
        losses.append(float(m["loss"]))
    change = np.asarray(capture._moved(params, capture.key(job.seed)))
    return Readings(tokens, losses, grad, change, list(capture.paths))


def fastest_choice(cell, first_seed: int):
    """The (technique, chips) the window would launch the cell's jobs
    on: the Trial Runner's fastest feasible one, profiled as a run's
    set-up profiles them."""
    from repro.core.api import SaturnSession
    from repro.core.job import ClusterSpec
    from repro.core.perfmodel import iter_job_profiles

    from harness.bench import model_config
    session = cell.traffic["session"]
    jobs = backlog.saturn_jobs(backlog.expand(cell.traffic, first_seed),
                               model_config(cell.config))
    sess = SaturnSession(ClusterSpec(
        nodes=1, gpus_per_node=cell.chips,
        restart_cost_s=float(session["restart_cost_s"])))
    sess.submit(jobs)
    sess.profile(**session["profile"])
    best = min((p.step_time_s, tech, g)
               for tech, g, p in iter_job_profiles(sess.profiles,
                                                   jobs[0].name)
               if p.feasible)
    return best[1], best[2]


def collect(cell, seeds: int, faulty_seeds: int, first_seed: int) -> dict:
    """Lower readings over ``seeds`` seeds, upper ones over the first
    ``faulty_seeds``; see the module's docstring."""
    import gc

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core.library import ParallelismLibrary
    from repro.parallelism.build import BuiltJob

    from harness.bench import model_config
    from harness.reference import family, first_steps
    from harness.window import Capture

    enable_compile_cache()
    conf, cfg = cell.config, model_config(cell.config)
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}",
          flush=True)
    technique, chips = fastest_choice(cell, first_seed)
    gc.collect()
    print(f"choice {technique} x{chips}", flush=True)
    # seed k reads job k of its run's backlog, so the readings cover
    # every learning rate the mix has
    jobs = []
    for k in range(seeds):
        specs = backlog.expand(cell.traffic, first_seed + k)
        jobs.append(specs[k % len(specs)])
    plan = ParallelismLibrary().get(technique).plan(cfg, chips)
    programs = {}

    def program(job):
        opt_cfg = backlog.saturn_jobs([job], cfg)[0].opt_cfg
        if opt_cfg not in programs:
            programs[opt_cfg] = BuiltJob(cfg, plan, opt_cfg,
                                         devices=jax.devices()[:chips])
        return programs[opt_cfg]

    capture = Capture(family(conf["family"]).spec(conf),
                      conf["optimizer"]["b1"])
    params, opt = program(jobs[0]).init(jax.random.PRNGKey(0))
    capture.warm(params, opt)
    del params, opt
    out = {"cell": cell.name, "technique": technique, "chips": chips,
           "device": dev.device_kind, "program": [], "control": [],
           "half_batch": [], "token": []}

    def ref(job, **kw):
        return first_steps(conf, job.seed, batch=job.batch, seq=job.seq,
                           lr=job.lr, total_steps=job.steps,
                           steps=correct.STEPS, **kw)

    def record(name, job, other, base, t0):
        g = correct.gaps(other, base)
        row = {"seed": job.seed, "lr": job.lr, **g,
               "losses": other.losses, "ref_losses": base.losses,
               "worst": correct.worst_leaves(other, base),
               "leaves": {"paths": base.paths,
                          "grad": [other.grad.tolist(), base.grad.tolist()],
                          "change": [other.change.tolist(),
                                     base.change.tolist()]}}
        out[name].append(row)
        print(f"{name} seed {job.seed} lr {job.lr}: {g} worst change "
              f"{row['worst']['change'][:2]} "
              f"({time.monotonic() - t0:.1f} s)", flush=True)

    for k, job in enumerate(jobs):
        t0 = time.monotonic()
        prog = program_first_steps(program(job), capture, cfg, job)
        base = ref(job)
        record("program", job, prog, base, t0)
        if k < faulty_seeds:
            t0 = time.monotonic()
            record("half_batch", job, program_first_steps(
                program(job), capture, cfg, job, fault="half_batch"),
                base, t0)
            for name, kw in (("control", {"precision": "bfloat16"}),
                             ("token", {"fault": "token"})):
                t0 = time.monotonic()
                record(name, job, ref(job, **kw), base, t0)
    summary = {}
    for name in ("program", "control", "half_batch", "token"):
        rows_ = out[name]
        if rows_:
            summary[name] = {
                k: (max if name == "program" else min)(r[k] for r in rows_)
                for k in correct.GAPS}
    out["summary"] = summary
    print("summary (program: largest; others: smallest) "
          + json.dumps(summary), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faulty-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 7)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from harness.bench import CACHE_DIR
    cache = os.path.join(spec.ROOT, CACHE_DIR)
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    out = collect(spec.load_cell(args.workload), args.seeds,
                  args.faulty_seeds, args.first_seed)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
