"""Data-parallel training steps whose gradients are summed across chips by
the TotientPerms multi-ring all-reduce, through the program's
``repro.train.steps.make_shardmap_dp_train_step``: parameters and AdamW
state replicated on every chip, one row of the batch per chip, gradients
synced by ``ppermute`` rings of the configuration's strides.

Everything else (weights from the seed, the prefetching feed, the first
steps in set-up, the window, the reference check over the whole global
batch on one chip) is the one-chip training runner's.
"""

from __future__ import annotations

from bench.runners import train

MUST_PASS = train.MUST_PASS
# Limits of this cell, set from its own readings on four chips (PERF.md):
# its int8 control reads a smaller loss gap than the one-chip cell's.
LIMITS = {"loss_gap": 7e-5, "grad_gap": 3e-3, "change_gap": 0.02}


class Runner(train.Runner):
    limits = LIMITS

    def build(self):
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.device_order import topoopt_mesh
        from repro.models import lm
        from repro.train.steps import make_shardmap_dp_train_step

        n = len(self.devices)
        mesh = topoopt_mesh((n,), ("data",), devices=np.asarray(self.devices))
        sync = self.config["sync"]
        step = make_shardmap_dp_train_step(
            self.cfg, self.opt, mesh, ring_strides=tuple(sync["ring_strides"]),
            schedule=sync["schedule"])
        p_specs = lm.param_specs(self.cfg)
        rep = NamedSharding(mesh, P())
        self._rows = NamedSharding(mesh, P("data"))
        p_sh = jax.tree.map(lambda _: rep, p_specs)
        o_sh = jax.tree.map(lambda _: rep, jax.eval_shape(self.opt.init, p_specs))
        return step, p_specs, p_sh, o_sh, mesh

    def put_batch(self, batch):
        import jax

        return jax.device_put(batch, self._rows)

    def call_step(self, step_i: int, batch):
        import jax.numpy as jnp

        self.params, self.opt_state, loss, _ = self.step(
            self.params, self.opt_state, self.put_batch(batch),
            jnp.int32(step_i), 0)
        return loss

    def fault_rows(self) -> dict:
        """The one-chip runner's faults, and the exchange between chips
        left out: the first chip's update then follows its own row alone."""
        return {**super().fault_rows(), "no_exchange": [0]}
