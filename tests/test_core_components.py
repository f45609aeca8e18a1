"""Parallelism Library registry, Trial Runner, checkpoint store, data
pipeline, MoE routing properties."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core.job import Job
from repro.core.library import ParallelismLibrary
from repro.core.profiler import (HARDWARE, TrialRunner,
                                 collective_bytes_from_hlo,
                                 hardware_for_kind, local_hardware)
from repro.parallelism.base import Plan, Technique


# ------------------------------------------------------ Parallelism Library

class _Custom(Technique):
    name = "my-custom"

    def search_space(self, cfg, n):
        return n == 2

    def plan(self, cfg, n):
        return Plan(self.name, n, (("data", n),), {"batch": "data"})


def test_library_register_and_candidates():
    lib = ParallelismLibrary()
    assert set(lib.names()) >= {"ddp", "fsdp", "tp", "gpipe", "remat-offload"}
    lib.register(_Custom())
    cfg = get_config("xlstm-125m").reduced()
    cands = lib.candidates(cfg, [1, 2, 4])
    assert ("my-custom", 2) in cands
    assert ("my-custom", 4) not in cands
    assert ("ddp", 1) in cands


def test_library_rejects_wrong_interface():
    lib = ParallelismLibrary()
    with pytest.raises(TypeError):
        lib.register(object())


def test_library_persistence(tmp_path):
    lib = ParallelismLibrary()
    p = str(tmp_path / "lib.json")
    lib.save(p)
    lib2 = ParallelismLibrary.load(p)
    assert set(lib2.names()) == set(lib.names())


# ------------------------------------------------------------ Trial Runner

def test_profiler_napkin_monotonic_and_cached(tmp_path):
    lib = ParallelismLibrary()
    runner = TrialRunner(lib, HARDWARE["a100"],
                         cache_path=str(tmp_path / "cache.json"))
    job = Job("t", get_config("stablelm-12b"), 16, 1024, 100)
    p1 = runner.profile(job, "fsdp", 2)
    p8 = runner.profile(job, "fsdp", 8)
    assert p8.step_time_s < p1.step_time_s, "more GPUs must model faster"
    assert p8.mem_per_device < p1.mem_per_device
    # cache: second runner reads the same numbers from disk (flushes
    # are batched now, so persist explicitly)
    runner.flush()
    runner2 = TrialRunner(lib, HARDWARE["a100"],
                          cache_path=str(tmp_path / "cache.json"))
    assert runner2.profile(job, "fsdp", 8).step_time_s == p8.step_time_s


def test_profiler_empirical_single_device(monkeypatch):
    lib = ParallelismLibrary()
    runner = TrialRunner(lib, HARDWARE["a100"])
    # a trial's memory is the compiled program's, never the napkin model's
    monkeypatch.setattr(runner, "_mem_estimate",
                        _raise(AssertionError("estimate used")))
    job = Job("e", get_config("xlstm-125m").reduced(), 2, 32, 10)
    prof = runner.profile(job, "ddp", 1, mode="empirical")
    assert prof.source == "empirical"
    assert prof.step_time_s > 0
    assert prof.feasible
    compiled = runner._compiled_step(job, lib.get("ddp").plan(job.cfg, 1))
    assert prof.mem_per_device == runner._compiled_mem(compiled) > 0


def test_infeasible_technique_marked():
    lib = ParallelismLibrary()
    runner = TrialRunner(lib, HARDWARE["a100"])
    job = Job("i", get_config("xlstm-125m").reduced(), 2, 32, 10)
    prof = runner.profile(job, "tp", 7)  # 4 heads % 7 != 0
    assert not prof.feasible


# -------------------------------------------- device hardware, trial errors

class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_hardware_for_device_kind():
    assert hardware_for_kind("TPU v5 lite") is HARDWARE["v5e"]
    with pytest.raises(ValueError, match="no HardwareSpec"):
        hardware_for_kind("TPU v99")


@pytest.mark.parametrize("kind,given,expect", [
    ("TPU v5 lite", None, "v5e"),
    ("TPU v5 lite", "v5e", "v5e"),
    ("TPU v5 lite", "a100", ValueError),    # contradicts the device
    ("TPU v99", None, ValueError),          # unknown kind
])
def test_local_hardware_on_tpu(monkeypatch, kind, given, expect):
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice("tpu", kind)])
    hw = HARDWARE[given] if given else None
    if expect is ValueError:
        with pytest.raises(ValueError):
            local_hardware(hw)
    else:
        assert local_hardware(hw) is HARDWARE[expect]


def test_local_hardware_off_tpu_keeps_given_spec():
    assert local_hardware() is HARDWARE["a100"]
    assert local_hardware(HARDWARE["v100"]) is HARDWARE["v100"]


def _tiny_job():
    return Job("e", get_config("xlstm-125m").reduced(), 2, 32, 10)


def _raise(err):
    def fn(*a, **k):
        raise err
    return fn


class _FailingBuilt:
    """A BuiltJob whose step raises ``err`` (an empirical trial)."""

    def __init__(self, err):
        self.err = err

    def init(self, key):
        return {}, {}

    def place_batch(self, batch):
        return batch

    def step(self, *args):
        raise self.err


OOM = jax.errors.JaxRuntimeError(
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm.")


@pytest.mark.parametrize("mode", ["analytic", "empirical"])
def test_out_of_memory_trial_is_infeasible(monkeypatch, mode):
    runner = TrialRunner(ParallelismLibrary(), HARDWARE["a100"])
    monkeypatch.setattr(runner, "_compiled_step", _raise(OOM))
    monkeypatch.setattr(runner, "_built_job",
                        lambda job, plan: _FailingBuilt(OOM))
    prof = runner.profile(_tiny_job(), "ddp", 1, mode=mode)
    assert not prof.feasible and prof.source == mode
    assert prof.step_time_s == float("inf")
    assert prof.terms == {"out_of_memory": 1.0}


@pytest.mark.parametrize("mode", ["analytic", "empirical"])
@pytest.mark.parametrize("err", [
    jax.errors.JaxRuntimeError("INTERNAL: RET_CHECK failure"),
    ValueError("bad shape"),
])
def test_other_trial_errors_raise(monkeypatch, mode, err):
    runner = TrialRunner(ParallelismLibrary(), HARDWARE["a100"])
    monkeypatch.setattr(runner, "_compiled_step", _raise(err))
    monkeypatch.setattr(runner, "_built_job",
                        lambda job, plan: _FailingBuilt(err))
    with pytest.raises(type(err)):
        runner.profile(_tiny_job(), "ddp", 1, mode=mode)


def test_analytic_beyond_local_devices_is_labelled_napkin(monkeypatch):
    runner = TrialRunner(ParallelismLibrary(), HARDWARE["a100"])
    monkeypatch.setattr(runner, "_compiled_step",
                        _raise(AssertionError("must not compile")))
    prof = runner.profile(_tiny_job(), "fsdp", len(jax.devices()) + 1,
                          mode="analytic")
    assert prof.source == "napkin" and prof.feasible
    assert 0 < prof.step_time_s < float("inf")


# ------------------------------------------------------ compile cache

@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_default_is_in_checkout(monkeypatch,
                                              restore_cache_dir):
    from repro import compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.CACHE_DIR == os.path.join(root, ".jax_cache")
    assert compile_cache.enable_compile_cache() == compile_cache.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_honours_env(monkeypatch, tmp_path,
                                   restore_cache_dir):
    from repro import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert os.listdir(tmp_path), "the compile did not land in the env dir"


def test_worker_imports_cache_without_scheduler():
    """Worker processes enable the cache without importing repro.core."""
    code = ("import sys, repro.compile_cache, repro.train.process_worker; "
            "sys.exit(any(m.startswith('repro.core') for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_collective_regex_parser():
    hlo = """
  %ag = bf16[8,128] all-gather(%x), dimensions={0}
  %ar = f32[1024] all-reduce(%y), to_apply=%add
"""
    out = collective_bytes_from_hlo(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 1024 * 4
    assert out["total"] == out["all-gather"] + out["all-reduce"]


# --------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint.store import (load_checkpoint, load_metadata,
                                        save_checkpoint)
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": [jnp.ones(4), {"c": jnp.zeros((2, 2), jnp.bfloat16)}]}
    path = str(tmp_path / "ck.npz")
    save_checkpoint(path, tree, {"step": 7})
    back = load_checkpoint(path, tree)
    for x, y in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
        assert x.dtype == y.dtype
    assert load_metadata(path)["step"] == 7


# ------------------------------------------------------------------- data

def test_data_deterministic():
    from repro.data.synthetic import SyntheticLM
    cfg = get_config("gemma3-4b").reduced()
    a = list(SyntheticLM(cfg, seed=3).batches(2, 16, num_batches=2))
    b = list(SyntheticLM(cfg, seed=3).batches(2, 16, num_batches=2))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    c = next(SyntheticLM(cfg, seed=4).batches(2, 16, num_batches=1))
    assert not np.array_equal(np.asarray(a[0]["tokens"]),
                              np.asarray(c["tokens"]))


def test_data_learnable_structure():
    """Bigram structure: next token equals perm[prev] most of the time."""
    from repro.data.synthetic import SyntheticLM
    cfg = get_config("h2o-danube-3-4b").reduced()
    src = SyntheticLM(cfg, seed=0, noise=0.2)
    b = next(src.batches(4, 128, num_batches=1))
    toks = np.asarray(b["tokens"])
    match = np.mean(src._perm[toks[:, :-1]] == toks[:, 1:])
    assert match > 0.6


# -------------------------------------------------------------------- MoE

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50))
def test_moe_router_weights_and_dropping(seed):
    """Combine weights are convex per token; dropped tokens only reduce
    output norm, never corrupt other tokens."""
    from repro.models.moe import _route_row, moe_capacity
    cfg = get_config("olmoe-1b-7b").reduced()
    m = cfg.moe
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    d = cfg.d_model
    xrow = jax.random.normal(k1, (32, d))
    p = {"router": jax.random.normal(k2, (d, m.num_experts)) * 0.1}
    cap = moe_capacity(cfg, 32)
    xg, tok, w, aux = _route_row(p, xrow, cfg, cap)
    w = np.asarray(w)
    assert (w >= 0).all() and w.max() <= 1.0 + 1e-6
    # every token's total routed weight <= 1 (== 1 unless dropped)
    tok = np.asarray(tok)
    sums = np.zeros(32)
    np.add.at(sums, tok.reshape(-1), w.reshape(-1))
    assert (sums <= 1.0 + 1e-5).all()
    assert float(aux) > 0


def test_moe_forward_matches_dense_when_one_expert():
    """With num_experts=1, top_k=1, MoE must equal a plain FFN."""
    import dataclasses
    from repro.models.moe import moe_ffn, moe_spec
    from repro.models.params import init_params
    cfg0 = get_config("olmoe-1b-7b").reduced()
    cfg = dataclasses.replace(
        cfg0, moe=dataclasses.replace(cfg0.moe, num_experts=1, top_k=1,
                                      capacity_factor=2.0))
    p = init_params(moe_spec(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    out, aux = moe_ffn(p, x, cfg)
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["wi_gate"][0]))
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"][0])
    dense = jnp.einsum("bsf,fd->bsd", g * u, p["wo"][0])
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=1e-5, rtol=1e-5)
