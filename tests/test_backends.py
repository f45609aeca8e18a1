"""Execution backends: the ExecutionBackend protocol, SimBackend
equivalence with the default path, the ObservedProfiles feedback
overlay, the LocalJaxBackend really training through the Schedule IR
(checkpointed preemption + resume), and the strict library load."""
import dataclasses
import os

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.baselines import CurrentPractice, OptimusDynamic
from repro.core.executor import simulate
from repro.core.job import ClusterSpec, Job
from repro.core.library import ParallelismLibrary
from repro.core.local_backend import LocalJaxBackend
from repro.core.perfmodel import ObservedProfiles
from repro.core.profiler import Profile
from repro.core.runtime import SimBackend
from repro.core.schedule import Policy, Schedule, ScheduleEntry

CFG = get_config("xlstm-125m").reduced()
# micro same-family variant: small enough that real CPU training steps
# are milliseconds and JIT compiles are a couple of seconds
MICRO = dataclasses.replace(CFG, d_model=64, num_heads=2, num_kv_heads=2,
                            head_dim=32, name="xlstm-micro")


def mk_workload(n_jobs=6, seed=0, total_gpus=8):
    rng = np.random.RandomState(seed)
    jobs, profiles = [], {}
    for i in range(n_jobs):
        j = Job(f"j{i}", CFG, 8, 64, total_steps=int(rng.randint(100, 400)))
        jobs.append(j)
        base = rng.uniform(1.0, 4.0)
        eff = rng.uniform(0.5, 0.95)
        g = 1
        while g <= total_gpus:
            for tech, mult in (("ddp", 1.0), ("fsdp", 1.1), ("gpipe", 1.25)):
                profiles[(j.name, tech, g)] = Profile(
                    j.name, tech, g, base * mult / g ** eff, 1e9, True, "t")
            g *= 2
    return jobs, profiles


CLUSTER = ClusterSpec(nodes=1, gpus_per_node=8, restart_cost_s=10.0)


# ------------------------------------------------ protocol / sim backend

def test_explicit_sim_backend_is_the_default():
    """simulate(exec_backend=SimBackend(...)) must be bit-identical to
    the default path (same noise seeding, same event semantics)."""
    jobs, profiles = mk_workload(n_jobs=6, seed=3)
    a = simulate(jobs, OptimusDynamic(), profiles, CLUSTER,
                 introspect_every_s=120, noise_sigma=0.3, noise_seed=7)
    b = simulate(jobs, OptimusDynamic(), profiles, CLUSTER,
                 introspect_every_s=120,
                 exec_backend=SimBackend(noise_sigma=0.3, noise_seed=7))
    assert a.makespan_s == b.makespan_s
    assert a.restarts == b.restarts
    assert a.replans == b.replans
    assert len(a.gantt) == len(b.gantt)


def test_sim_result_stats_empty_for_sim():
    jobs, profiles = mk_workload(n_jobs=3, seed=1)
    res = simulate(jobs, CurrentPractice(), profiles, CLUSTER)
    assert res.stats == {}


# ------------------------------------------------- observed-profile view

def test_observed_profiles_overlay():
    _, profiles = mk_workload(n_jobs=2, seed=0)
    key = ("j0", "ddp", 2)
    obs = ObservedProfiles(profiles, {key: 123.0})
    assert obs[key].step_time_s == 123.0
    assert obs[key].source == "observed"
    # untouched combos pass through, the base is not mutated
    other = ("j1", "ddp", 2)
    assert obs[other].step_time_s == profiles[other].step_time_s
    assert profiles[key].step_time_s != 123.0
    # Mapping contract: same keys, same size
    assert set(obs) == set(profiles)
    assert len(obs) == len(profiles)


def test_observed_profiles_key_normalization():
    """Default-class 4-tuple and 3-tuple keys hit the same observation
    (single-class PerfModels answer both shapes)."""
    _, profiles = mk_workload(n_jobs=1, seed=0)
    obs = ObservedProfiles(profiles, {("j0", "ddp", 1): 9.0})
    assert obs[("j0", "ddp", 1)].step_time_s == 9.0


# --------------------------------------------------- local JAX execution

def _local_workload(n_jobs, steps, est=0.01):
    jobs = [Job(f"j{i}", MICRO, 2, 32, total_steps=steps, lr=1e-3, seed=i)
            for i in range(n_jobs)]
    profiles = {}
    for j in jobs:
        for tech in ("ddp", "remat-offload"):
            profiles[(j.name, tech, 1)] = Profile(
                j.name, tech, 1, est, 1e9, True, "t")
    return jobs, profiles


LOCAL_CLUSTER = ClusterSpec(nodes=1, gpus_per_node=1, restart_cost_s=0.5)


@pytest.mark.slow
def test_local_backend_trains_schedule_for_real(tmp_path):
    """A 3-job workload really trains through the Schedule IR: every
    job runs its exact step budget, checkpoints land on disk, and
    measured step times feed the observation channel."""
    jobs, profiles = _local_workload(n_jobs=3, steps=12)
    be = LocalJaxBackend(ckpt_dir=str(tmp_path))
    res = simulate(jobs, CurrentPractice(), profiles, LOCAL_CLUSTER,
                   exec_backend=be)
    assert {g.job for g in res.gantt if g.kind == "run"} == \
        {j.name for j in jobs}
    assert res.makespan_s > 0
    first = min((g for g in res.gantt if g.kind == "run"),
                key=lambda g: g.start_s).job
    for j in jobs:
        st = res.stats[j.name]
        assert sum(s["steps"] for s in st["segments"]) == j.total_steps
        # the loss trajectory is real numbers from real training
        assert all(np.isfinite(loss) for _, loss in st["losses"])
        assert os.path.exists(tmp_path / f"{j.name}.npz")
        seg = st["segments"][0]
        assert seg["spans"]["step.dispatch"]["n"] == seg["steps"]
    # the first job's first step (program load) is kept out of the
    # measured step rate; the segment's compile seconds come from its
    # spans.  The other jobs share its program and load nothing.
    seg = res.stats[first]["segments"][0]
    assert seg["first_step_s"] > seg["measured_step_s"]
    assert seg["compile_s"] > seg["measured_step_s"]
    for j in jobs:
        if j.name != first:
            assert res.stats[j.name]["segments"][0]["compile_s"] < \
                seg["compile_s"] / 10
    assert be.observed, "measured step times must reach the feedback dict"
    for v in be.observed.values():
        assert 0 < v < 10


class FlipWhenProgressed(Policy):
    """Dynamic policy that changes j0's technique at the first replan
    that observes real progress — guaranteeing a mid-run
    preempt/checkpoint/restart with a non-trivial resume point."""

    name = "flip"
    dynamic = True
    replan_on_completion = False

    def __init__(self, total_steps):
        self.total = total_steps
        self.flipped = False

    def plan(self, jobs, remaining, profiles, cluster, current):
        if remaining.get("j0", self.total) < self.total:
            self.flipped = True
        tech = "remat-offload" if self.flipped else "ddp"
        return Schedule([ScheduleEntry(
            j.name, tech if j.name == "j0" else "ddp", 1) for j in jobs])


@pytest.mark.slow
def test_local_backend_preempt_checkpoint_resume(tmp_path):
    """An introspection replan preempts the running job; it must
    checkpoint, pay the restart penalty, resume from the saved step
    with the data stream continued, and finish its exact budget."""
    steps = 1500
    jobs, profiles = _local_workload(n_jobs=1, steps=steps)
    be = LocalJaxBackend(ckpt_dir=str(tmp_path))
    res = simulate(jobs, FlipWhenProgressed(steps), profiles,
                   LOCAL_CLUSTER, introspect_every_s=1.0, exec_backend=be)
    assert res.restarts >= 1
    segs = res.stats["j0"]["segments"]
    assert len(segs) >= 2 and segs[0]["preempted"]
    # resume continuity: each segment starts exactly where the previous
    # one checkpointed, and the budget is met in total
    for a, b in zip(segs, segs[1:]):
        assert b["start_step"] == a["start_step"] + a["steps"]
    assert sum(s["steps"] for s in segs) == steps
    assert segs[0]["steps"] > 0, "flip fired before any observed progress"
    assert segs[0]["technique"] == "ddp"
    assert segs[-1]["technique"] == "remat-offload"
    # the run segments around the restart respect the real penalty
    restarts = [g for g in res.gantt if g.kind == "restart"]
    assert len(restarts) == res.restarts
    for r in restarts:
        assert abs((r.end_s - r.start_s)
                   - LOCAL_CLUSTER.restart_cost_s) < 1e-9
    # losses were recorded across the boundary and stayed finite
    losses = res.stats["j0"]["losses"]
    assert len(losses) == steps
    assert all(np.isfinite(loss) for _, loss in losses)
    steps_logged = [s for s, _ in losses]
    assert steps_logged == sorted(steps_logged)
    assert steps_logged[0] == 1 and steps_logged[-1] == steps


def test_local_worker_failure_surfaces_and_quarantines(tmp_path):
    """An exception escaping a worker thread must reach the engine as a
    detected worker failure (never a silent hang in wait_until): the
    job is retried under its budget, then quarantined with the reason,
    and the run completes."""
    from repro.core.chaos import RetryPolicy

    class Boom:
        name = "boom"

        def search_space(self, cfg, n):
            return n == 1

        def plan(self, cfg, n):
            raise RuntimeError("poisoned technique")

    lib = ParallelismLibrary()
    lib.register(Boom())
    jobs = [Job("j0", MICRO, 2, 32, total_steps=50, lr=1e-3, seed=0)]
    # the only profile j0 has is the poisoned technique: every launch
    # of it dies inside the worker thread
    profiles = {("j0", "boom", 1): Profile("j0", "boom", 1, 0.01, 1e9,
                                           True, "t")}
    be = LocalJaxBackend(
        library=lib, ckpt_dir=str(tmp_path),
        retry_policy=RetryPolicy(budget=1, base_s=0.1, cap_s=0.2,
                                 jitter=0.0))
    res = simulate(jobs, CurrentPractice(), profiles, LOCAL_CLUSTER,
                   exec_backend=be)
    # budget 1: original + one retry fail, then quarantine
    assert res.worker_failures == 2
    assert res.restarts == 1
    assert "j0" in res.quarantined
    assert "retry budget exhausted" in res.quarantined["j0"]
    assert "poisoned technique" in res.quarantined["j0"]
    seg = res.stats["j0"]["segments"][0]
    assert seg["failed"] and "poisoned technique" in seg["failed"]


# ------------------------------------------------------ session plumbing

def test_session_rejects_unknown_backend():
    from repro.core.api import SaturnSession
    sess = SaturnSession(CLUSTER)
    with pytest.raises(ValueError):
        sess.run(backend="remote")
    with pytest.raises(ValueError):
        sess.run(backend="sim", ckpt_dir="/tmp/x")


# ------------------------------------------------------ library loading

def test_library_load_strict_raises_on_missing(tmp_path):
    lib = ParallelismLibrary()

    class Custom:
        name = "my-custom"

        def search_space(self, cfg, n):
            return n == 1

        def plan(self, cfg, n):
            raise NotImplementedError

    lib.register(Custom())
    p = str(tmp_path / "lib.json")
    lib.save(p)
    # default pool lacks "my-custom": strict load must name it
    with pytest.raises(KeyError, match="my-custom"):
        ParallelismLibrary.load(p)
    lax = ParallelismLibrary.load(p, strict=False)
    assert "my-custom" not in lax.names()
    assert "ddp" in lax.names()
    full = ParallelismLibrary.load(p, available=list(
        dict(lib.items()).values()))
    assert "my-custom" in full.names()
