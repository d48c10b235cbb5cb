"""Step builders: jit-able train_step / serve_step with sharding attached.

Two execution styles:

* ``pjit`` (default, used by the dry-run and the big-mesh path): the step is
  written in global terms; GSPMD inserts the collectives implied by the
  sharding plan (FSDP all-gathers, gradient reduce-scatters, EP all-to-all).
* ``shard_map_dp`` (examples/tests): explicit data-parallel trainer whose
  gradient sync is the paper's multi-ring TotientPerms AllReduce
  (core.collectives), matching the NCCL integration of §6.

Both return the jitted step inside :class:`RecordedStep`, which records each
call's host dispatch and the executables it built (``repro.telemetry``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..configs.base import ArchConfig, ShapeSpec, cache_specs, input_specs
from ..core.collectives import topoopt_psum_fn
from ..models import lm
from ..optim import Optimizer
from ..parallel.act_sharding import ActivationPolicy, set_policy
from ..parallel.sharding import (
    ShardingPlan,
    batch_sharding,
    opt_state_sharding,
    param_sharding,
)


class RecordedStep:
    """A jitted train step whose every call is a ``train.dispatch`` span:
    the host's work to launch the step (flattening the arguments, copying
    host arrays to the device, and compiling where the call needs a new
    executable).  The span carries the ``train.compiles`` counter: the
    entries the call added to the jit's cache, each an executable compiled
    or loaded from the persistent cache, or now and then a new signature
    of one it had (the same sharding spelled another way).  ``last`` is the
    newest call's record.  Every other attribute (``lower``, ``trace``, ...)
    is the jit's own; donation is the jit's."""

    def __init__(self, jitted):
        self._jitted = jitted
        self.last: telemetry.Span | None = None

    def __call__(self, *args, **kwargs):
        before = self._jitted._cache_size()
        with telemetry.span("train.dispatch") as self.last:
            out = self._jitted(*args, **kwargs)
            telemetry.count("train.compiles",
                            self._jitted._cache_size() - before)
        return out

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def install_activation_policy(plan: ShardingPlan, mesh: Mesh) -> None:
    """GSPMD hints: batch-over-data activations (see parallel.act_sharding)."""
    set_policy(
        ActivationPolicy(
            dp=plan.dp_axes(mesh),
            tp="model" if "model" in mesh.axis_names else None,
            seq="model" if plan.seq_parallel else None,
            mesh=mesh,
        )
    )


def make_train_step(cfg: ArchConfig, optimizer: Optimizer, plan: ShardingPlan):
    """Global-semantics train step (pjit style)."""

    def train_step(params, opt_state, batch, step):
        def loss(p):
            return lm.loss_fn(
                p, batch, cfg, remat=plan.remat, loss_chunk=plan.loss_chunk
            )

        (total, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
        new_params, new_state = optimizer.update(grads, opt_state, params, step)
        gnorm = jnp.sqrt(
            sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
        )
        metrics = dict(metrics, loss=total, grad_norm=gnorm)
        return new_params, new_state, metrics

    return train_step


def make_serve_step(cfg: ArchConfig, shape: ShapeSpec):
    if shape.kind == "prefill":
        def serve_step(params, batch):
            return lm.prefill(params, batch, cfg)
        return serve_step

    def serve_step(params, batch):
        return lm.decode_step(params, batch, cfg)

    return serve_step


def shapes_of(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def jit_train_step(
    cfg: ArchConfig,
    optimizer: Optimizer,
    plan: ShardingPlan,
    mesh: Mesh,
    donate: bool = True,
):
    """jit(train_step) with in/out shardings derived from the plan.

    Returns (step, (param_specs, opt_specs, param_shardings,
    opt_shardings, batch_fn)): ``step`` is the jit as a
    :class:`RecordedStep`; batch_fn maps a ShapeSpec to that cell's batch
    shardings."""
    install_activation_policy(plan, mesh)
    p_specs = lm.param_specs(cfg)
    o_specs = jax.eval_shape(optimizer.init, p_specs)
    p_sh = param_sharding(p_specs, plan, mesh)
    o_sh = opt_state_sharding(o_specs, plan, mesh)

    step_fn = make_train_step(cfg, optimizer, plan)

    def batch_sh(shape: ShapeSpec):
        b = input_specs(cfg, shape)
        return batch_sharding(b, cfg, plan, mesh)

    jitted = jax.jit(
        step_fn,
        in_shardings=(p_sh, o_sh, None, None),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return RecordedStep(jitted), (p_specs, o_specs, p_sh, o_sh, batch_sh)


# ---------------------------------------------------------------------------
# shard_map data-parallel trainer with TotientPerms multi-ring gradient sync
# ---------------------------------------------------------------------------


def make_shardmap_dp_train_step(
    cfg: ArchConfig,
    optimizer: Optimizer,
    mesh: Mesh,
    axis_name: str = "data",
    ring_strides: tuple[int, ...] = (1,),
    compressor=None,
    schedule: str = "ring",
):
    """The §6 trainer: per-device microbatch, local grads, gradient sync via
    the collective schedule the co-optimizer searched (``Strategy.schedule``):
    multi-ring TotientPerms AllReduce by default, recursive halving-doubling
    or multi-tree when the plan says so (optionally int8-compressed — the
    compressor path is ring-only and ignores ``schedule``).

    Params/opt-state replicated; batch sharded on ``axis_name``.
    ``compressor``: parallel.compression.Compressor or None.
    """
    n = mesh.shape[axis_name]
    sync = topoopt_psum_fn(
        tuple(ring_strides), axis_name, schedule=schedule, group_size=n
    )

    def step(params, opt_state, batch, step_idx, residual):
        def loss(p):
            return lm.loss_fn(p, batch, cfg, remat="full")

        (total, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)

        if compressor is not None:
            # residual leaves carry a leading device axis (sharded state).
            local_res = jax.tree.map(lambda r: r[0], residual)
            grads, new_res = compressor.sync(
                grads, local_res, axis_name, ring_strides
            )
            residual = jax.tree.map(lambda r: r[None], new_res)
        else:
            grads = jax.tree.map(lambda g: sync(g) / n, grads)
        new_params, new_state = optimizer.update(grads, opt_state, params, step_idx)
        total = jax.lax.pmean(total, axis_name)
        return new_params, new_state, total, residual

    rep = P()
    sharded = P(axis_name)
    smapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(rep, rep, sharded, rep, sharded if compressor else rep),
        out_specs=(rep, rep, rep, sharded if compressor else rep),
        check_vma=False,
    )
    return RecordedStep(jax.jit(smapped))


def init_compressor_residual(compressor, params, mesh, axis_name="data"):
    """Per-device residual state: leaves (n_devices, *param.shape)."""
    n = mesh.shape[axis_name]
    import jax.numpy as jnp

    return jax.tree.map(
        lambda p: jnp.zeros((n, *p.shape), jnp.float32), params
    )
