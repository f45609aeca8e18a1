"""Parallelism-equivalence checker.

For each technique valid at ``n_devices``, one real train step on the
first ``n_devices`` devices must match the single-device baseline.  On
the CPU it runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (so the main pytest
process keeps 1 device); ``chip_smoke.py --chips 4`` runs it on a TPU
host at full width.

Usage: python -m repro.testing.parallel_check [arch_id]
"""
import os
import sys

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def check(cfg, n_devices: int, tol: float = 2e-2) -> int:
    """Returns the number of techniques whose step did not match."""
    from repro.configs import concrete_batch
    from repro.models.transformer import init_model
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.parallelism.build import BuiltJob
    from repro.parallelism.techniques import DEFAULT_TECHNIQUES
    from repro.train.steps import make_train_step

    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    key = jax.random.PRNGKey(42)
    batch = concrete_batch(cfg, 8, 32)

    # single-device baseline
    params0 = init_model(cfg, key)
    opt0 = init_opt_state(params0)
    p_ref, o_ref, m_ref = jax.jit(make_train_step(cfg, opt_cfg))(
        params0, opt0, batch)
    ref_loss = float(m_ref["loss"])
    print(f"[baseline] {cfg.name} loss={ref_loss:.6f}")

    failures = 0
    for tech in DEFAULT_TECHNIQUES:
        if not tech.search_space(cfg, n_devices):
            print(f"[{tech.name}] not in search space for {cfg.name}@{n_devices} — skipped")
            continue
        plan = tech.plan(cfg, n_devices)
        job = BuiltJob(cfg, plan, opt_cfg)
        params, opt = job.init(key)
        b = job.place_batch(batch)
        p1, o1, m1 = job.step(params, opt, b)
        loss = float(m1["loss"])
        # compare updated params against baseline update
        diffs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32))))
                 for a, b_ in zip(jax.tree.leaves(p1), jax.tree.leaves(p_ref))]
        max_diff = max(diffs)
        ok = abs(loss - ref_loss) < tol and max_diff < tol
        print(f"[{tech.name}] loss={loss:.6f} dloss={abs(loss-ref_loss):.2e} "
              f"max_param_diff={max_diff:.2e} {'OK' if ok else 'FAIL'}")
        if not ok:
            failures += 1
    return failures


if __name__ == "__main__":
    from repro.configs import get_config
    arch = sys.argv[1] if len(sys.argv) > 1 else "h2o-danube-3-4b"
    sys.exit(check(get_config(arch).reduced(num_layers=4), 8))
