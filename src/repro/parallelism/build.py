"""Turn a (model config, Plan) pair into an executable: mesh, shardings,
and a jitted train step.  Used by the Trial Runner (profiling), the local
executor (real runs) and reused by the launch path.

The programs are shared: one jitted step and one pair of init programs
per (model config, plan, static optimizer fields, devices), kept for the
whole process.  A job's own hyperparameters (``adamw.HYPERPARAMS``) are
operands of that step, so the jobs of a sweep over lr, seeds and lengths
trace, lower and load their program once between them.
"""
from __future__ import annotations

import collections
import dataclasses
import threading

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import tracing
from ..models.config import ModelConfig
from ..models.transformer import model_spec
from ..optim.adamw import AdamWConfig, init_opt_state, split_hyperparams
from ..train.steps import make_train_step
from .base import Plan
from .context import axis_rules
from .pipeline import make_pipeline_loss
from .shardings import (make_mesh_from_plan,
                        opt_state_shardings, param_shardings)

PROGRAMS_KEPT = 32       # built programs kept, least recently used dropped


class _Program:
    """What the jobs of one (model, plan, static optimizer fields,
    devices) share: the mesh, the shardings, the jitted step
    ``(params, opt_state, batch, hyper)`` and the init programs."""

    def __init__(self, cfg: ModelConfig, plan: Plan, opt_static: AdamWConfig,
                 devices):
        self.mesh = mesh = make_mesh_from_plan(plan, devices)
        self.spec_tree = model_spec(cfg)
        self.p_sh = param_shardings(self.spec_tree, plan, mesh)
        self.o_sh = opt_state_shardings(self.spec_tree, plan, mesh)
        ax = plan.rules.get("batch")
        self.batch_sh = NamedSharding(
            mesh, PartitionSpec() if ax is None else PartitionSpec(ax))
        self.hyper_sh = NamedSharding(mesh, PartitionSpec())
        if plan.technique == "gpipe":
            base = make_train_step(cfg, opt_static,
                                   loss_fn=make_pipeline_loss(cfg, plan, mesh))
        else:
            base = make_train_step(cfg, opt_static, remat=plan.remat)

        def step(params, opt_state, batch, hyper):
            with axis_rules(plan.rules, mesh):
                return base(params, opt_state, batch, hyper)

        options = None
        if plan.remat and plan.n_devices > 1 and \
                mesh.devices.flat[0].platform == "tpu":
            # libtpu's latency-hiding scheduler emits an invalid schedule
            # (RET_CHECK in hlo_schedule.cc) for a rematerialized layer
            # scan around the mLSTM chunk loop with the batch sharded
            # over several chips; such steps compile without it
            options = {"xla_tpu_enable_latency_hiding_scheduler": "false"}
        self.step = jax.jit(
            step,
            in_shardings=(self.p_sh, self.o_sh, self.batch_sh,
                          self.hyper_sh),
            out_shardings=(self.p_sh, self.o_sh, None),
            compiler_options=options,
        )
        self.init_opt = jax.jit(init_opt_state, out_shardings=self.o_sh)
        self._init_params = {}

    def init_params(self, dtype):
        """The jitted ``key -> params`` for ``dtype``."""
        fn = self._init_params.get(dtype)
        if fn is None:
            from ..models.params import init_params
            spec = self.spec_tree
            fn = self._init_params.setdefault(dtype, jax.jit(
                lambda k: init_params(spec, k, dtype),
                out_shardings=self.p_sh))
        return fn


def _plan_key(plan: Plan) -> tuple:
    """``plan``'s fields, hashable (its rules are a dict)."""
    return tuple(tuple(sorted(v.items())) if isinstance(v, dict) else v
                 for v in (getattr(plan, f.name)
                           for f in dataclasses.fields(plan)))


class _Programs:
    """This process's built programs, the least recently used dropped
    past ``PROGRAMS_KEPT``.  Each lookup counts ``program.builds`` or
    ``program.reuses`` on the caller's innermost span."""

    def __init__(self):
        self._table: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()

    def get(self, cfg: ModelConfig, plan: Plan, opt_static: AdamWConfig,
            devices) -> _Program:
        key = (cfg, _plan_key(plan), opt_static, tuple(devices))
        with self._lock:
            prog = self._table.get(key)
            if prog is not None:
                self._table.move_to_end(key)
                tracing.count("program.reuses")
                return prog
            prog = self._table[key] = _Program(cfg, plan, opt_static, devices)
            while len(self._table) > PROGRAMS_KEPT:
                self._table.popitem(last=False)
        tracing.count("program.builds")
        return prog

    def clear(self) -> None:
        with self._lock:
            self._table.clear()


PROGRAMS = _Programs()


class _Bound:
    """A shared jitted step, or what its ``lower`` and ``compile`` return,
    with one job's hyperparameters bound: called and lowered as
    ``(params, opt_state, batch)``; every other attribute is the wrapped
    object's."""

    def __init__(self, fn, built: "BuiltJob"):
        self._fn, self._built = fn, built

    def __call__(self, params, opt_state, batch):
        return self._fn(params, opt_state, batch, self._built.hyper)

    def lower(self, params, opt_state, batch):
        # the hyperparameters' avals and sharding are all lowering needs
        sh = self._built._program.hyper_sh
        hyper = {k: jax.ShapeDtypeStruct((), jnp.float32, sharding=sh)
                 for k in self._built._hyper}
        return _Bound(self._fn.lower(params, opt_state, batch, hyper),
                      self._built)

    def compile(self, *args, **kwargs):
        return _Bound(self._fn.compile(*args, **kwargs), self._built)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class BuiltJob:
    """Executable artifact for one (model, technique, n_devices) choice,
    with one job's optimizer settings: a view of the shared program that
    passes the job's hyperparameters to it."""

    def __init__(self, cfg: ModelConfig, plan: Plan, opt_cfg: AdamWConfig,
                 devices=None):
        self.cfg, self.plan, self.opt_cfg = cfg, plan, opt_cfg
        if devices is None:
            devices = jax.devices()[:plan.n_devices]
        opt_static, self._hyper = split_hyperparams(opt_cfg)
        self._program = prog = PROGRAMS.get(cfg, plan, opt_static, devices)
        self.mesh, self.spec_tree = prog.mesh, prog.spec_tree
        self.p_sh, self.o_sh = prog.p_sh, prog.o_sh
        self._hyper_args = None
        self._step = None

    # ------------------------------------------------------------- build
    def _make_step(self):
        return _Bound(self._program.step, self)

    @property
    def hyper(self):
        """The job's hyperparameters as float32 device scalars, made on
        first use."""
        if self._hyper_args is None:
            sh = self._program.hyper_sh
            self._hyper_args = {k: jax.device_put(v, sh)
                                for k, v in self._hyper.items()}
        return self._hyper_args

    @property
    def step(self):
        if self._step is None:
            self._step = self._make_step()
        return self._step

    # ----------------------------------------------------------- helpers
    def init(self, key, dtype=jnp.float32):
        """Initialize params + opt state with the plan's shardings."""
        prog = self._program
        with self.mesh:
            params = prog.init_params(dtype)(key)
            opt = prog.init_opt(params)
        return params, opt

    def place_batch(self, batch):
        sh = self._program.batch_sh
        return jax.tree.map(lambda x: jax.device_put(x, sh), batch)

    def lower(self, batch_specs, params_abstract, opt_abstract):
        """Lower + compile without execution (profiling / dry-run)."""
        return self.step.lower(params_abstract, opt_abstract, batch_specs)
