"""LM training as a self-tunable PS job.

Wraps the big-model substrate (repro.models + repro.ps.stepfn) in the same
job interface the paper workloads use, so the TuningManager can drive real
LM training: Type II knobs re-jit the step; ``mesh_split`` (Type I-b)
relocates the parameter/optimizer shards onto a new (dp, tp) mesh — via ODMR
(in-memory resharding under the new specs) or the checkpoint+restore
baseline, per the plan's method.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.core.knobs import Knob, KnobSpace
from repro.core.reconfig import ReconfigPlan
from repro.data.synthetic import lm_batch_iterator
from repro.distributed.sharding import (MeshSpec, param_shardings,
                                       param_specs)
from repro.launch.mesh import make_meshspec
from repro.models import lm
from repro.optim import make_optimizer
from repro.ps import odmr
from repro.ps.stepfn import StepKnobs, build_train_step
from repro.ps.trainer import make_staleness_adapter


def lm_knob_space(n_devices: int = 1) -> KnobSpace:
    knobs = [
        Knob("microbatches", "ordinal", (1, 2, 4)),
        Knob("remat", "nominal", ("none", "dots", "full")),
        Knob("compression", "nominal", ("none", "bf16", "int8")),
        Knob("staleness", "ordinal", (0, 1, 2)),
        Knob("k_chunk", "ordinal", (256, 512, 1024)),
    ]
    if n_devices > 1:
        splits, dp = [], 1
        while dp <= n_devices:
            if n_devices % dp == 0:
                splits.append(f"{dp}x{n_devices // dp}")
            dp *= 2
        knobs.append(Knob("mesh_split", "nominal", tuple(splits)))
    return KnobSpace(tuple(knobs))


DEFAULT_LM_SETTING = {"microbatches": 1, "remat": "none",
                      "compression": "none", "staleness": 0, "k_chunk": 512}


def setting_to_stepknobs(setting: dict) -> StepKnobs:
    return StepKnobs(
        microbatches=setting.get("microbatches", 1),
        remat=setting.get("remat", "none"),
        compression=setting.get("compression", "none"),
        staleness=setting.get("staleness", 0),
        k_chunk=setting.get("k_chunk", 1024),
        ce_chunk=setting.get("ce_chunk", 0),
        donate=False,   # the driver owns buffer lifetime across reconfigs
    )


class LMJob:
    def __init__(self, cfg: ModelConfig, tc: TrainConfig | None = None,
                 batch: int = 8, seq: int = 128, seed: int = 0,
                 n_devices: int | None = None):
        self.cfg = cfg
        self.tc = tc or TrainConfig()
        self.batch, self.seq, self.seed = batch, seq, seed
        self.n_devices = n_devices or len(jax.devices())
        self._ms_cache: dict[str, MeshSpec] = {}
        self.eps = 1.0   # drivers override

    # ------------------------------------------------------------------ mesh
    def meshspec(self, setting: dict) -> MeshSpec:
        split = setting.get("mesh_split", f"{self.n_devices}x1")
        if split not in self._ms_cache:
            dp, tp = (int(x) for x in split.split("x"))
            self._ms_cache[split] = make_meshspec(dp, tp)
        return self._ms_cache[split]

    # ----------------------------------------------------------------- state
    def init_state(self, setting: dict, seed: int = 0):
        def make():
            params = lm.init_params(self.cfg, jax.random.PRNGKey(seed))
            opt_init, _ = make_optimizer(self.tc)
            state = {"params": params, "opt": opt_init(params),
                     "step": jnp.zeros((), jnp.int32)}
            s = setting.get("staleness", 0)
            if s > 0:
                state["grad_queue"] = jax.tree_util.tree_map(
                    lambda p: jnp.zeros((s,) + p.shape, jnp.bfloat16),
                    params)
            return state

        ms = self.meshspec(setting)
        if ms.n_devices == 1:
            return make()
        # built in place under its shardings: a published-width state is
        # larger than any one device, so it never exists unsharded
        shapes = jax.eval_shape(make)
        return jax.jit(make, out_shardings=param_shardings(shapes, ms))()

    def _place(self, state, setting):
        ms = self.meshspec(setting)
        if ms.n_devices == 1:
            return state
        specs = param_specs(state, ms)
        return odmr.relocate_now(state, specs, ms)

    def _checkpoint_restore(self, state, setting):
        """The baseline's round trip: save to disk, restore straight into
        the new placement (each leaf goes host -> its shards)."""
        import tempfile
        from repro.checkpoint import restore_pytree, save_pytree
        ms = self.meshspec(setting)
        template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        with tempfile.TemporaryDirectory() as d:
            save_pytree(state, d, step=0)
            state, _ = restore_pytree(
                template, d, step=0, ms=ms if ms.n_devices > 1 else None)
        return state

    # ------------------------------------------------------------------ step
    def step_builder(self, setting: dict):
        ms = self.meshspec(setting)
        knobs = setting_to_stepknobs(setting)
        return build_train_step(self.cfg, self.tc, ms if ms.n_devices > 1
                                else None, knobs)

    # --------------------------------------------------------------- adapter
    def state_adapter(self, state, plan: ReconfigPlan):
        state = make_staleness_adapter(jnp.bfloat16)(state, plan)
        if "I-b" in plan.kinds:
            if plan.method == "odmr":
                state = self._place(state, plan.new)
            else:                       # baseline: CKP + MDR round trip
                state = self._checkpoint_restore(state, plan.new)
        return state

    # ------------------------------------------------------------------ data
    def batches(self, seed: int = 0):
        return lm_batch_iterator(self.cfg, self.batch, self.seq, seed)
