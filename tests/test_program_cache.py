"""Shared step programs: jobs that differ only in their hyperparameters
(lr, weight decay, warmup, length) or seed share one jitted step and one
pair of init programs per model, plan and devices; the hyperparameters
are operands, so the shared step computes what the step with the job's
constants bound computes; the checkpoint's tree is unchanged."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.checkpoint.store import save_checkpoint
from repro.configs import get_config
from repro.core.baselines import CurrentPractice
from repro.core.executor import simulate
from repro.core.job import ClusterSpec, Job
from repro.core.library import ParallelismLibrary
from repro.core.local_backend import LocalJaxBackend
from repro.core.profiler import Profile
from repro.models.params import init_params
from repro.models.transformer import model_spec
from repro.optim.adamw import AdamWConfig
from repro.parallelism.build import BuiltJob
from repro.train.steps import make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MICRO = dataclasses.replace(get_config("xlstm-125m").reduced(), d_model=64,
                            num_heads=2, num_kv_heads=2, head_dim=32,
                            name="xlstm-micro")
LIB = ParallelismLibrary()
BATCH = {"tokens": np.arange(2 * 32, dtype=np.int32).reshape(2, 32) % 97}


def _plan(technique="ddp", cfg=MICRO):
    return LIB.get(technique).plan(cfg, 1)


def _first_step(built, seed):
    params, opt = built.init(jax.random.PRNGKey(seed))
    return jax.block_until_ready(
        built.step(params, opt, built.place_batch(BATCH)))


def test_jobs_that_differ_in_hyperparameters_share_one_program():
    a = Job("a", MICRO, 2, 32, total_steps=40, lr=1e-4, seed=0)
    b = Job("b", MICRO, 2, 32, total_steps=90, lr=1e-3, seed=1)
    assert a.opt_cfg.warmup_steps != b.opt_cfg.warmup_steps
    seen = {}
    for job in (a, b):
        with tracing.span("test.build") as build:
            built = BuiltJob(job.cfg, _plan(), job.opt_cfg)
        with tracing.span("test.first_step") as first:
            _, _, m = _first_step(built, job.seed)
        seen[job.name] = (built, build.counters, first.counters or {}, m)
    built_a, build_a, first_a, m_a = seen["a"]
    built_b, build_b, first_b, m_b = seen["b"]
    assert build_a == {"program.builds": 1}
    assert first_a["compile.trace_s"] > 0
    assert build_b == {"program.reuses": 1}
    assert built_b._program is built_a._program
    # the second job's init and first step neither trace nor compile
    assert not any(k.startswith("compile.") for k in first_b), first_b
    # each job's step used its own schedule: lr x (step + 1) / warmup
    assert float(m_a["lr"]) == pytest.approx(1e-4 / a.opt_cfg.warmup_steps)
    assert float(m_b["lr"]) == pytest.approx(1e-3 / b.opt_cfg.warmup_steps)


@pytest.mark.parametrize("change", ["model", "plan", "static optimizer"])
def test_another_model_plan_or_optimizer_gets_its_own_program(change):
    cfg, technique, opt_cfg = MICRO, "ddp", AdamWConfig()
    first = BuiltJob(cfg, _plan(technique, cfg), opt_cfg)
    if change == "model":
        cfg = dataclasses.replace(MICRO, d_model=32, head_dim=16)
    elif change == "plan":
        technique = "remat-offload"
    else:
        opt_cfg = AdamWConfig(b2=0.999)
    with tracing.span("test.build") as build:
        other = BuiltJob(cfg, _plan(technique, cfg), opt_cfg)
    assert build.counters == {"program.builds": 1}
    assert other._program is not first._program


def test_another_device_set_gets_its_own_program():
    """Two host devices, in a process of their own."""
    code = """
import dataclasses, jax
from repro import tracing
from repro.configs import get_config
from repro.core.library import ParallelismLibrary
from repro.optim.adamw import AdamWConfig
from repro.parallelism.build import BuiltJob
cfg = dataclasses.replace(get_config("xlstm-125m").reduced(), d_model=64,
                          num_heads=2, num_kv_heads=2, head_dim=32)
plan = ParallelismLibrary().get("ddp").plan(cfg, 1)
d0, d1 = jax.devices()
built = [BuiltJob(cfg, plan, AdamWConfig(), devices=[d])
         for d in (d0, d1, d0)]
print(tracing.counters()["program.builds"],
      tracing.counters()["program.reuses"],
      built[0]._program is built[2]._program,
      built[0]._program is built[1]._program)
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == ["2", "1", "True", "False"]


def _steps(fn, params, opt, n):
    for i in range(n):
        batch = {"tokens": (BATCH["tokens"] + i) % 97}
        params, opt, _ = fn(params, opt, batch)
    return params, opt


@pytest.mark.parametrize("schedule", ["cosine", "linear", "constant"])
@pytest.mark.parametrize("lr", [1e-4, 1e-3])
def test_the_shared_step_computes_the_bound_step(lr, schedule):
    """Three steps across the warmup's end (warmup 2, 6 steps in all):
    the shared program with the job's hyperparameters as operands, and
    the step with them bound as constants, agree."""
    opt_cfg = AdamWConfig(lr=lr, schedule=schedule, warmup_steps=2,
                          total_steps=6, weight_decay=0.1)
    built = BuiltJob(MICRO, _plan(), opt_cfg)
    params, opt = built.init(jax.random.PRNGKey(3))
    got = _steps(built.step, params, opt, 3)
    want = _steps(jax.jit(make_train_step(MICRO, opt_cfg)), params, opt, 3)
    assert int(got[1]["step"]) == 3
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)


def test_jobs_differing_only_in_lr_end_apart():
    """The shared program binds no job's hyperparameters: a second job
    stepped through it trains at its own lr."""
    slow = BuiltJob(MICRO, _plan(), AdamWConfig(lr=1e-4, warmup_steps=1))
    fast = BuiltJob(MICRO, _plan(), AdamWConfig(lr=1e-3, warmup_steps=1))
    assert fast._program is slow._program
    params, opt = slow.init(jax.random.PRNGKey(0))
    a, _ = _steps(slow.step, params, opt, 2)
    b, _ = _steps(fast.step, params, opt, 2)
    moved = [float(jnp.max(jnp.abs(x - y)))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    assert max(moved) > 1e-4


def _run(tmp_path, jobs, resume=False):
    profiles = {(j.name, "ddp", 1): Profile(j.name, "ddp", 1, 0.01, 1e9,
                                            True, "t") for j in jobs}
    be = LocalJaxBackend(ckpt_dir=str(tmp_path), resume=resume)
    return simulate(jobs, CurrentPractice(), profiles,
                    ClusterSpec(nodes=1, gpus_per_node=1,
                                restart_cost_s=0.5), exec_backend=be)


def _build_counters(name):
    """The counters of the ``build`` span of ``name``'s one segment."""
    done = tracing.spans()
    launch, = [s for s in done if s.name == "launch"
               and s.attrs.get("job") == name]
    seg, = [s for s in done if s.name == "segment" and s.parent == launch.id]
    build, = [s for s in done if s.name == "build" and s.parent == seg.id]
    return build.counters


def _parent_format(params, step):
    """The tree every earlier version saved: params, then AdamW's mu, nu
    and step, and nothing else."""
    zeros = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), params)
    return {"params": params,
            "opt": {"mu": zeros, "nu": zeros, "step": np.int32(step)}}


def test_a_local_backend_switch_reuses_the_program(tmp_path):
    jobs = [Job("cache-j0", MICRO, 2, 32, total_steps=3, lr=1e-4, seed=0),
            Job("cache-j1", MICRO, 2, 32, total_steps=3, lr=1e-3, seed=1)]
    res = _run(tmp_path, jobs)
    first, second = (res.stats[j.name]["segments"] for j in jobs)
    assert len(first) == len(second) == 1
    assert _build_counters("cache-j0") == {"program.builds": 1}
    assert _build_counters("cache-j1") == {"program.reuses": 1}
    assert first[0]["compile_s"] > 0.1
    assert second[0]["compile_s"] < 0.01
    # the checkpoint holds the tree it always held: no hyperparameters
    params = init_params(model_spec(MICRO), jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path / "parent.npz"), _parent_format(params, 3),
                    {"step": 3})
    with np.load(tmp_path / "parent.npz") as z:
        want = set(z.files)
    for j in jobs:
        with np.load(tmp_path / f"{j.name}.npz") as z:
            assert set(z.files) == want


def test_a_checkpoint_in_the_earlier_format_resumes_at_its_step(tmp_path):
    job = Job("cache-resume", MICRO, 2, 32, total_steps=6, lr=1e-3, seed=0)
    params = init_params(model_spec(MICRO), jax.random.PRNGKey(7))
    save_checkpoint(str(tmp_path / f"{job.name}.npz"),
                    _parent_format(params, 4), {"step": 4, "loss": 1.0})
    res = _run(tmp_path, [job], resume=True)
    seg = res.stats[job.name]["segments"][0]
    assert seg["failed"] is None
    assert seg["start_step"] == 4 and seg["steps"] > 0
    losses = [loss for _, loss in res.stats[job.name]["losses"]]
    assert losses and all(np.isfinite(losses))
