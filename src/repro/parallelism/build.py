"""Turn a (model config, Plan) pair into an executable: mesh, shardings,
and a jitted train step.  Used by the Trial Runner (profiling), the local
executor (real runs) and reused by the launch path.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..models.config import ModelConfig
from ..models.transformer import model_spec
from ..optim.adamw import AdamWConfig
from ..train.steps import make_train_step
from .base import Plan
from .context import axis_rules
from .pipeline import make_pipeline_loss
from .shardings import (make_mesh_from_plan,
                        opt_state_shardings, param_shardings)


class BuiltJob:
    """Executable artifact for one (model, technique, n_devices) choice."""

    def __init__(self, cfg: ModelConfig, plan: Plan, opt_cfg: AdamWConfig,
                 devices=None):
        self.cfg, self.plan, self.opt_cfg = cfg, plan, opt_cfg
        self.mesh = make_mesh_from_plan(plan, devices)
        self.spec_tree = model_spec(cfg)
        self.p_sh = param_shardings(self.spec_tree, plan, self.mesh)
        self.o_sh = opt_state_shardings(self.spec_tree, plan, self.mesh)
        self._step = None

    # ------------------------------------------------------------- build
    def _make_step(self):
        cfg, plan, mesh = self.cfg, self.plan, self.mesh
        if plan.technique == "gpipe":
            loss_fn = make_pipeline_loss(cfg, plan, mesh)
            base = make_train_step(cfg, self.opt_cfg, loss_fn=loss_fn)
        else:
            base = make_train_step(cfg, self.opt_cfg, remat=plan.remat)

        def step(params, opt_state, batch):
            with axis_rules(plan.rules, mesh):
                return base(params, opt_state, batch)

        options = None
        if plan.remat and plan.n_devices > 1 and \
                self.mesh.devices.flat[0].platform == "tpu":
            # libtpu's latency-hiding scheduler emits an invalid schedule
            # (RET_CHECK in hlo_schedule.cc) for a rematerialized layer
            # scan around the mLSTM chunk loop with the batch sharded
            # over several chips; such steps compile without it
            options = {"xla_tpu_enable_latency_hiding_scheduler": "false"}
        return jax.jit(
            step,
            in_shardings=(self.p_sh, self.o_sh, self._batch_sh_tree()),
            out_shardings=(self.p_sh, self.o_sh, None),
            compiler_options=options,
        )

    def _batch_axis(self):
        return self.plan.rules.get("batch")

    def _batch_sh_tree(self):
        ax = self._batch_axis()
        if ax is None:
            return NamedSharding(self.mesh, PartitionSpec())
        return NamedSharding(self.mesh, PartitionSpec(ax))

    @property
    def step(self):
        if self._step is None:
            self._step = self._make_step()
        return self._step

    # ----------------------------------------------------------- helpers
    def init(self, key, dtype=jnp.float32):
        """Initialize params + opt state with the plan's shardings."""
        from ..models.params import init_params
        from ..optim.adamw import init_opt_state
        with self.mesh:
            params = jax.jit(
                lambda k: init_params(self.spec_tree, k, dtype),
                out_shardings=self.p_sh)(key)
            opt = jax.jit(init_opt_state, out_shardings=self.o_sh)(params)
        return params, opt

    def place_batch(self, batch):
        sh = self._batch_sh_tree()
        return jax.tree.map(lambda x: jax.device_put(x, sh), batch)

    def lower(self, batch_specs, params_abstract, opt_abstract):
        """Lower + compile without execution (profiling / dry-run)."""
        return self.step.lower(params_abstract, opt_abstract, batch_specs)
