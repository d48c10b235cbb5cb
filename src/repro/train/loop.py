"""Fault-tolerant training loop.

- checkpoint every N steps (atomic), resume from latest on start,
- deterministic stateless data pipeline (restart-safe),
- straggler detection: each step's ``train.step`` span (dispatch and loss
  read) vs the median of the last 50; slow steps are counted and surfaced
  (on a real pod this feeds the backup-worker / TopoOpt link-repair path),
- a step after the first whose dispatch built an executable is logged,
- failure injection hook for tests (``fail_at``) proving restart works.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..checkpoint.ckpt import latest_step, load_checkpoint, prune_checkpoints, save_checkpoint
from ..configs.base import ArchConfig, ShapeSpec
from ..data.pipeline import DataSpec, Prefetcher
from ..models import lm
from ..optim import Optimizer
from ..parallel.sharding import ShardingPlan
from .steps import jit_train_step


class InjectedFailure(RuntimeError):
    pass


@dataclass
class TrainResult:
    final_step: int
    losses: list[float] = field(default_factory=list)
    straggler_steps: int = 0
    restarts: int = 0
    # The final parameters and the last step's metrics, as device arrays.
    params: Any = None
    metrics: dict | None = None


def train(
    cfg: ArchConfig,
    shape: ShapeSpec,
    optimizer: Optimizer,
    plan: ShardingPlan,
    mesh,
    total_steps: int,
    ckpt_dir: str | None = None,
    ckpt_every: int = 50,
    seed: int = 0,
    fail_at: int | None = None,
    straggler_factor: float = 3.0,
    log_every: int = 10,
    logger=print,
) -> TrainResult:
    jitted, (p_specs, o_specs, p_sh, o_sh, _) = jit_train_step(
        cfg, optimizer, plan, mesh, donate=True
    )

    start_step = 0
    params = opt_state = None
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        start_step, params, opt_state, _ = load_checkpoint(
            ckpt_dir, p_specs, o_specs,
            param_shardings=p_sh, opt_shardings=o_sh,
        )
        logger(f"[loop] resumed from step {start_step}")

    if params is None:
        with mesh:
            params = jax.jit(
                lambda: lm.init(jax.random.PRNGKey(seed), cfg),
                out_shardings=p_sh,
            )()
            opt_state = jax.jit(optimizer.init, out_shardings=o_sh)(params)

    data = Prefetcher(DataSpec(cfg=cfg, shape=shape, seed=seed), start_step)
    result = TrainResult(final_step=start_step)

    try:
        step = start_step
        while step < total_steps:
            got_step, batch = data.next()
            assert got_step == step, f"pipeline desync {got_step} != {step}"
            with (jax.profiler.StepTraceAnnotation("train", step_num=step),
                  telemetry.span("train.step", step) as rec):
                with mesh:
                    params, opt_state, metrics = jitted(
                        params, opt_state, batch, jnp.int32(step)
                    )
                with telemetry.span("train.loss_read", step):
                    loss = float(metrics["loss"])
            result.metrics = metrics
            built = jitted.last.counts.get("train.compiles", 0)
            if built and step > start_step:
                logger(f"[loop] step {step} built {built} executable(s)")
            dt = rec.seconds
            done = step - start_step + 1
            med = float(np.median(
                [r.seconds for r in telemetry.recent("train.step", min(done, 50))]))
            if done > 5 and dt > straggler_factor * med:
                result.straggler_steps += 1
                logger(f"[loop] straggler at step {step}: {dt:.3f}s vs median {med:.3f}s")

            result.losses.append(loss)
            if step % log_every == 0:
                logger(f"[loop] step {step} loss {loss:.4f} ({dt*1e3:.1f} ms)")

            step += 1
            result.final_step = step

            if ckpt_dir and step % ckpt_every == 0:
                save_checkpoint(ckpt_dir, step, params, opt_state)
                prune_checkpoints(ckpt_dir, keep=3)

            if fail_at is not None and step == fail_at:
                raise InjectedFailure(f"injected failure at step {step}")
    finally:
        data.close()

    if ckpt_dir:
        save_checkpoint(ckpt_dir, result.final_step, params, opt_state)
    result.params = params
    return result
