"""The comparison that decides ``correct``, at tiny sizes on the CPU:
sound runs agree with the reference, the control (the reference in
bfloat16) fails the real cells' limits, and a run whose timed path is
broken underneath comes out not correct, once for each fault a
one-chip training cell can have."""
import contextlib
import time

import jax
import pytest

import tiny
from harness import bench, correct
from harness.reference import first_steps

SEED = 2 ** 31 + 99


def _gaps(family, **kw):
    cfg = tiny.config(family)
    run = dict(batch=4, seq=32, lr=1e-3, total_steps=8)
    base = first_steps(cfg, 11, **run)
    return correct.gaps(first_steps(cfg, 11, **run, **kw), base)


@pytest.mark.parametrize("family", sorted(tiny.CELLS))
def test_control_fails_the_cells_limits(family):
    g = _gaps(family, precision="bfloat16")
    lim = tiny.limits(family)
    assert any(g[k] > lim[k] for k in lim), (g, lim)


@pytest.mark.parametrize("family", sorted(tiny.CELLS))
@pytest.mark.parametrize("fault", ["token"])
def test_planted_reference_faults_fail_the_cells_limits(family, fault):
    g = _gaps(family, fault=fault)
    lim = tiny.limits(family)
    assert any(g[k] > lim[k] for k in lim), (g, lim)


@contextlib.contextmanager
def _broken(fault):
    """Break the system under test's timed path underneath the run."""
    from repro.data import synthetic
    from repro.parallelism.build import BuiltJob
    saved = []

    def patch(owner, name, value):
        saved.append((owner, name, getattr(owner, name)))
        setattr(owner, name, value)

    make = BuiltJob._make_step
    if fault == "state_unchanged":
        def make_step(self):
            step = make(self)

            def frozen(params, opt, batch):
                _, _, metrics = step(params, opt, batch)
                return params, opt, metrics
            return jax.jit(frozen)
        patch(BuiltJob, "_make_step", make_step)
    elif fault == "half_batch":
        # inside the step, after the window has read the tokens: the
        # second half of the rows repeats the first, so the loss and its
        # gradient are the mean over half the batch
        def make_step(self):
            step = make(self)

            def half(params, opt, batch):
                t = batch["tokens"]
                h = t.shape[0] // 2
                return step(params, opt,
                            {**batch, "tokens": t.at[h:].set(t[:h])})
            return jax.jit(half)
        patch(BuiltJob, "_make_step", make_step)
    elif fault == "token":
        raw = synthetic.SyntheticLM._raw_batch

        def altered(self, rng, batch, seq):
            out = raw(self, rng, batch, seq)
            out["tokens"][:, 1] = (out["tokens"][:, 1] + 1) % \
                self.cfg.vocab_size
            return out
        patch(synthetic.SyntheticLM, "_raw_batch", altered)
    try:
        yield
    finally:
        for owner, name, value in reversed(saved):
            setattr(owner, name, value)


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch",
                                   "token"])
def test_a_broken_timed_path_is_not_correct(fault):
    cell = tiny.cell("xlstm")
    with _broken(fault) if fault else contextlib.nullcontext():
        out = bench.run_cell(cell, SEED, 6.0, False, time.time())
    assert out["correct"] is (fault is None), out["checks"]
    assert out["metrics"]["sweep_tokens_per_s"]["value"] > 0


def test_gaps_of_identical_readings_are_zero_and_faults_count():
    cfg = tiny.config("xlstm")
    run = dict(batch=4, seq=32, lr=1e-3, total_steps=8)
    base = first_steps(cfg, 3, **run)
    assert all(v == 0 for v in correct.gaps(base, base).values())
    moved = first_steps(cfg, 3, **run, fault="token")
    g = correct.gaps(moved, base)
    assert g["batch_mismatch"] == correct.STEPS
    assert set(g) == set(correct.GAPS)
