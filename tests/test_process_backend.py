"""ProcessJaxBackend: per-job worker processes supervised over pipes —
clean multi-process training, real fault injection (SIGKILL mid-step,
stalled heartbeats, truncated checkpoints) with bit-for-bit verified
recovery, quarantine on budget exhaustion, and crash-then-resume across
backend lifetimes."""
import dataclasses
import os

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.baselines import CurrentPractice
from repro.core.chaos import ChaosTrace, RetryPolicy, WorkerFault
from repro.core.executor import simulate
from repro.core.job import ClusterSpec, Job
from repro.core.process_backend import ProcessJaxBackend
from repro.core.profiler import Profile

CFG = get_config("xlstm-125m").reduced()
MICRO = dataclasses.replace(CFG, d_model=64, num_heads=2, num_kv_heads=2,
                            head_dim=32, name="xlstm-micro")
CLUSTER = ClusterSpec(nodes=1, gpus_per_node=1, restart_cost_s=0.5)
STEPS = 400   # faults below strike on the first checkpoint at step 5
              # (WorkerFault.min_step), deep mid-run at this budget


def mk_jobs(n_jobs=1, steps=STEPS):
    jobs = [Job(f"j{i}", MICRO, 2, 32, total_steps=steps, lr=1e-3, seed=i)
            for i in range(n_jobs)]
    profiles = {(j.name, "ddp", 1): Profile(j.name, "ddp", 1, 0.01, 1e9,
                                            True, "t") for j in jobs}
    return jobs, profiles


def trajectory(res, name):
    """Absolute step -> loss, last write wins: steps replayed after a
    salvage overwrite their pre-crash records, leaving the trajectory
    training actually converged on."""
    d = {}
    for s, v in res.stats[name]["losses"]:
        d[s] = v
    return d


@pytest.fixture(scope="module")
def baseline(tmp_path_factory):
    """One uninterrupted 400-step run: the reference loss trajectory
    every recovery below must reproduce exactly."""
    jobs, profiles = mk_jobs()
    be = ProcessJaxBackend(
        ckpt_dir=str(tmp_path_factory.mktemp("base")), ckpt_every_steps=5)
    res = simulate(jobs, CurrentPractice(), profiles, CLUSTER,
                   exec_backend=be)
    assert res.worker_failures == 0 and res.quarantined == {}
    return trajectory(res, "j0")


def test_bind_refuses_tpu_before_spawning(monkeypatch):
    """A coordinator on a TPU host holds the chips: bind must fail loudly
    instead of spawning workers that cannot open them."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    be = ProcessJaxBackend()
    jobs, profiles = mk_jobs()
    with pytest.raises(RuntimeError, match="TPU"):
        be.bind(jobs, profiles, CLUSTER)
    assert not hasattr(be, "_monitor_thread")


@pytest.mark.slow
def test_process_backend_trains_for_real(tmp_path):
    """Two jobs really train in separate OS processes through the
    Schedule IR: exact step budgets, real finite losses, checkpoints on
    disk, measured step times in the feedback channel."""
    jobs, profiles = mk_jobs(n_jobs=2, steps=40)
    be = ProcessJaxBackend(ckpt_dir=str(tmp_path))
    res = simulate(jobs, CurrentPractice(), profiles, CLUSTER,
                   exec_backend=be)
    assert res.worker_failures == 0 and res.quarantined == {}
    for j in jobs:
        st = res.stats[j.name]
        assert sum(s["steps"] for s in st["segments"]) == j.total_steps
        assert len(st["losses"]) == j.total_steps
        assert all(np.isfinite(v) for _, v in st["losses"])
        assert os.path.exists(tmp_path / f"{j.name}.npz")
    assert be.observed
    for v in be.observed.values():
        assert 0 < v < 10


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["sigkill", "hang", "corrupt"])
def test_fault_recovery_matches_baseline_bit_for_bit(kind, tmp_path,
                                                     baseline):
    """Inject a real fault mid-run; the supervisor must detect it
    (process sentinel / heartbeat deadline / checksum), salvage the
    durable checkpoint, relaunch under backoff, and land the EXACT
    uninterrupted loss trajectory — recovery that loses or perturbs
    steps cannot hide."""
    jobs, profiles = mk_jobs()
    be = ProcessJaxBackend(ckpt_dir=str(tmp_path), ckpt_every_steps=5)
    res = simulate(jobs, CurrentPractice(), profiles, CLUSTER,
                   exec_backend=be,
                   chaos=ChaosTrace((WorkerFault(1.0, kind, "j0",
                                                 min_step=5),)))
    assert res.worker_failures >= 1
    assert res.restarts >= 1
    assert res.quarantined == {}
    segs = res.stats["j0"]["segments"]
    assert len(segs) >= 2 and segs[0]["failed"]
    # the relaunch resumed from the durable checkpoint, not step 0 and
    # not the victim's in-memory progress
    assert segs[-1]["start_step"] + segs[-1]["steps"] == STEPS
    got = trajectory(res, "j0")
    assert set(got) == set(baseline)
    assert max(abs(got[s] - baseline[s]) for s in baseline) == 0.0


@pytest.mark.slow
def test_budget_exhaustion_quarantines(tmp_path):
    """With a zero retry budget the first SIGKILL quarantines the job:
    the run completes (no deadlock, no raise) with the reason
    recorded and the durable progress preserved on disk."""
    jobs, profiles = mk_jobs()
    be = ProcessJaxBackend(ckpt_dir=str(tmp_path), ckpt_every_steps=5,
                           retry_policy=RetryPolicy(budget=0))
    res = simulate(jobs, CurrentPractice(), profiles, CLUSTER,
                   exec_backend=be,
                   chaos=ChaosTrace((WorkerFault(1.0, "sigkill", "j0",
                                                 min_step=5),)))
    assert res.worker_failures == 1
    assert "j0" in res.quarantined
    assert "retry budget exhausted" in res.quarantined["j0"]
    assert "SIGKILL" in res.quarantined["j0"]
    seg = res.stats["j0"]["segments"][0]
    assert seg["failed"] and seg["steps"] < STEPS


@pytest.mark.slow
def test_crash_then_resume_across_backends(tmp_path, baseline):
    """Verified crash recovery across process AND coordinator
    lifetimes: a run killed mid-flight leaves a durable checkpoint; a
    fresh backend with resume=True continues from exactly that step and
    the union of both trajectories is the uninterrupted one,
    bit for bit."""
    from repro.checkpoint.store import verify_checkpoint

    jobs, profiles = mk_jobs()
    be1 = ProcessJaxBackend(ckpt_dir=str(tmp_path), ckpt_every_steps=5,
                            retry_policy=RetryPolicy(budget=0))
    r1 = simulate(jobs, CurrentPractice(), profiles, CLUSTER,
                  exec_backend=be1,
                  chaos=ChaosTrace((WorkerFault(1.0, "sigkill", "j0",
                                                min_step=5),)))
    assert "j0" in r1.quarantined
    durable = int(verify_checkpoint(str(tmp_path / "j0.npz"))["step"])
    assert 0 < durable < STEPS

    be2 = ProcessJaxBackend(ckpt_dir=str(tmp_path), ckpt_every_steps=5,
                            resume=True)
    r2 = simulate(jobs, CurrentPractice(), profiles, CLUSTER,
                  exec_backend=be2)
    assert r2.worker_failures == 0 and r2.quarantined == {}
    segs = r2.stats["j0"]["segments"]
    assert segs[0]["start_step"] == durable
    assert sum(s["steps"] for s in segs) == STEPS - durable

    merged = trajectory(r1, "j0")
    merged.update(trajectory(r2, "j0"))
    assert set(merged) == set(baseline)
    assert max(abs(merged[s] - baseline[s]) for s in baseline) == 0.0
