"""Smoke run of Saturn's main path on TPU chips, through the entry points
a user calls: ``SaturnSession.submit`` -> ``profile`` (empirical trials)
-> ``run(backend="local")`` (solver + runtime + real training).

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # one four-chip host

One chip: the Pallas kernels at xlstm-125m widths against their jnp
references, then an lr x batch sweep of the full-width xlstm-125m
(random weights from each job's seed) profiled, planned and trained on
the chip.  Four chips: the same sweep on four chips, and one full-width
step of each technique valid at four chips against the one-chip step.

Everything runs in this one process (a chip belongs to one process).
The script exits non-zero and prints no result when JAX finds no TPU,
and when any phase fails.  The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SWEEP_LRS = (1e-4, 1e-3)
SWEEP_BATCHES = (4, 16)
SWEEP_SEQ = 1024
SWEEP_STEPS = 40
INTROSPECT_S = 30.0


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(msg)


def check_kernels(cfg, batch: int, seq: int) -> None:
    """Each Pallas kernel, compiled for the chip at ``cfg``'s widths,
    against its jnp reference (float32, highest matmul precision), at
    the bf16 tolerances of ``tests/test_kernels.py`` (the float32 sLSTM
    at 1e-3: the kernel's matmuls run at the chip's default
    precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.mlstm_chunk import mlstm_chunk
    from repro.kernels.rglru_scan import rglru_scan
    from repro.kernels.slstm_step import slstm_step_scan
    from repro.models.slstm_scan import slstm_scan

    h, d, r = cfg.num_heads, cfg.head_dim, cfg.d_model
    ks = jax.random.split(jax.random.PRNGKey(0), 10)
    bf = jnp.bfloat16
    f32 = lambda *xs: tuple(x.astype(jnp.float32) for x in xs)  # noqa: E731
    q = (jax.random.normal(ks[0], (batch, seq, h, d)) * d ** -0.5).astype(bf)
    k = jax.random.normal(ks[1], (batch, seq, h, d)).astype(bf)
    v = jax.random.normal(ks[2], (batch, seq, h, d)).astype(bf)
    ig = jax.random.normal(ks[3], (batch, seq, h)).astype(bf)
    fg = (jax.random.normal(ks[4], (batch, seq, h)) * 2 + 2).astype(bf)
    a = (jax.nn.sigmoid(jax.random.normal(ks[5], (batch, seq, r))) * 0.2
         + 0.8).astype(bf)
    b = (jax.random.normal(ks[6], (batch, seq, r)) * 0.1).astype(bf)
    gates = jax.random.normal(ks[7], (batch, seq, h, d, 4)) * 0.5
    rec = {n: jax.random.normal(kk, (h, d, d)) * 0.05
           for n, kk in zip(("rz", "ri", "rf", "ro"), jax.random.split(ks[8], 4))}
    init = (jnp.zeros((batch, h, d)), jnp.zeros((batch, h, d)),
            jnp.full((batch, h, d), -1e30), jnp.zeros((batch, h, d)))
    cases = {
        "flash_attention": (lambda: flash_attention(q, k, v),
                            lambda: ref.attention_ref(*f32(q, k, v)),
                            2e-2, 2e-2),
        "mlstm_chunk": (lambda: mlstm_chunk(q, k, v, ig, fg),
                        lambda: ref.mlstm_ref(*f32(q, k, v, ig, fg)),
                        5e-2, 5e-2),
        "rglru_scan": (lambda: rglru_scan(a, b),
                       lambda: ref.rglru_scan_ref(*f32(a, b)), 2e-2, 1e-2),
        "slstm_step_scan": (
            lambda: slstm_step_scan(gates, rec["rz"], rec["ri"], rec["rf"],
                                    rec["ro"]),
            lambda: jnp.swapaxes(slstm_scan(
                rec, jnp.swapaxes(gates, 0, 1), init)[1], 0, 1),
            1e-3, 1e-3),
    }
    for name, (kernel, reference, atol, rtol) in cases.items():
        t0 = time.perf_counter()
        out = np.asarray(jax.block_until_ready(kernel()), np.float32)
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = np.asarray(reference(), np.float32)
        err = float(np.max(np.abs(out - want)))
        print(f"  kernel {name} {tuple(out.shape)}: max|err| {err:.3g} "
              f"(first call incl. compile {dt:.2f}s)", flush=True)
        np.testing.assert_allclose(out, want, atol=atol, rtol=rtol,
                                   err_msg=name)


def sweep(cfg, chips: int, *, batches=SWEEP_BATCHES, seq: int = SWEEP_SEQ,
          steps: int = SWEEP_STEPS, introspect_s: float = INTROSPECT_S):
    """Profile, plan and train an lr x batch sweep of ``cfg`` on
    ``chips`` devices through ``SaturnSession``.  Returns the jobs, the
    run result and the seconds each phase took."""
    from repro.core.api import SaturnSession
    from repro.core.job import ClusterSpec, hpo_grid

    phases = {}
    jobs = hpo_grid([(cfg.name, cfg)], SWEEP_LRS, batches, seq_len=seq,
                    total_steps=steps)
    sess = SaturnSession(ClusterSpec(nodes=1, gpus_per_node=chips,
                                     restart_cost_s=2.0))
    print(f"  planning with hardware {sess.runner.hw}", flush=True)
    sess.submit(jobs)
    t0 = time.perf_counter()
    profiles = sess.profile(mode="empirical", strategy="exhaustive")
    phases["profile"] = time.perf_counter() - t0
    for (name, tech, g), p in sorted(profiles.items()):
        state = "feasible" if p.feasible else \
            f"infeasible {sorted(p.terms)}"
        print(f"  profile {name} {tech}x{g}: {p.step_time_s:.4f} s/step "
              f"{p.mem_per_device / 1e9:.2f} GB/chip source={p.source} "
              f"{state}", flush=True)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="saturn_smoke_") as ckpt:
        res = sess.run(backend="local", ckpt_dir=ckpt,
                       introspect_every_s=introspect_s)
    phases["run"] = time.perf_counter() - t0
    return jobs, res, phases


def check_sweep(jobs, res) -> None:
    """Every job ran its ``total_steps`` with finite losses, and no
    batch-16 job ran on ddp x 1, which does not fit one v5e chip."""
    print(f"  makespan {res.makespan_s:.1f}s replans={res.replans} "
          f"restarts={res.restarts} worker_failures={res.worker_failures}",
          flush=True)
    require(not res.quarantined, f"quarantined jobs: {res.quarantined}")
    for job in jobs:
        st = res.stats.get(job.name)
        require(st is not None, f"{job.name}: never ran")
        for s in st["segments"]:
            rate = (job.batch_size * job.seq_len / s["measured_step_s"]
                    if s["measured_step_s"] else float("nan"))
            print(f"  {job.name}: {s['technique']}x{s['n_gpus']} steps "
                  f"{s['start_step']}..{s['start_step'] + s['steps']} "
                  f"compile_s={s['compile_s']:.2f} "
                  f"step_s={s['measured_step_s']} tokens/s={rate:.0f} "
                  f"preempted={s['preempted']} failed={s['failed']}",
                  flush=True)
            require(s["failed"] is None, f"{job.name}: {s['failed']}")
            require(not (job.batch_size == 16 and s["technique"] == "ddp"
                         and s["n_gpus"] == 1),
                    f"{job.name} was planned on ddp x 1")
        reached = max(s["start_step"] + s["steps"] for s in st["segments"])
        losses = [v for _, v in st["losses"]]
        require(reached == job.total_steps,
                f"{job.name}: reached step {reached} of {job.total_steps}")
        require(losses and all(math.isfinite(v) for v in losses),
                f"{job.name}: non-finite loss")
        print(f"  {job.name}: loss {losses[0]:.4f} -> {losses[-1]:.4f}",
              flush=True)


def check_shared_chips(res) -> None:
    """At least one job ran on more than one chip, and two jobs ran at
    the same time on disjoint chips."""
    runs = [g for g in res.gantt if g.kind == "run"]
    for g in runs:
        print(f"  gantt {g.job}: {g.technique}x{g.n_gpus} chips "
              f"{tuple(g.devices)} {g.start_s:.1f}..{g.end_s:.1f}s",
              flush=True)
    require(any(g.n_gpus > 1 for g in runs), "no job ran on several chips")
    require(any(a.job != b.job and a.start_s < b.end_s
                and b.start_s < a.end_s
                and not set(a.devices) & set(b.devices)
                for a in runs for b in runs),
            "no two jobs ran at once on disjoint chips")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels + sweep on one chip; 4: the sweep on "
                         "four chips + each technique against one chip")
    args = ap.parse_args()

    import jax

    from repro.compile_cache import cache_dir
    from repro.configs import get_config

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir()}", flush=True)
    cfg = get_config("xlstm-125m")
    phases = {}
    t0 = time.perf_counter()
    if args.chips == 1:
        check_kernels(cfg, batch=2, seq=SWEEP_SEQ)
        phases["kernels"] = time.perf_counter() - t0
        jobs, res, sweep_phases = sweep(cfg, 1)
        phases.update(sweep_phases)
        check_sweep(jobs, res)
    else:
        from repro.testing.parallel_check import check
        jobs, res, sweep_phases = sweep(cfg, 4)
        phases.update(sweep_phases)
        check_sweep(jobs, res)
        check_shared_chips(res)
        t0 = time.perf_counter()
        failures = check(cfg, 4)
        phases["equivalence"] = time.perf_counter() - t0
        require(failures == 0, f"{failures} techniques differ from one chip")
    peaks = [d.memory_stats().get("peak_bytes_in_use") for d in devices]
    print(f"peak_bytes_in_use per chip: {peaks}", flush=True)
    entries = len(os.listdir(cache_dir())) if os.path.isdir(cache_dir()) \
        else 0
    print(f"compile cache entries in {cache_dir()}: {entries}", flush=True)
    print("phase seconds: " + ", ".join(f"{k} {v:.1f}"
                                        for k, v in phases.items()),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
