"""The plain references agree with the program where both compute the same
thing in the same precision."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.ref import minicpm, pricing

from .helpers import TINY_MODEL


def _tiny():
    import json

    from bench import core

    cfg = json.loads((core.BENCH_DIR / "configs" / "minicpm-2b.json").read_text())
    cfg.update(TINY_MODEL)
    return cfg


def test_minicpm_reference_matches_the_program_in_float32():
    from bench.runners import train as train_runner
    from repro.models import lm

    cfg = _tiny()
    dm = minicpm.dims(cfg)
    prog_cfg = dataclasses.replace(train_runner.program_config(cfg),
                                   param_dtype="float32",
                                   activation_dtype="float32")
    p0 = minicpm.init_params(minicpm.key_for(2**31 + 3), dm, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, dm["vocab"], (2, 24)),
                         jnp.int32)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda p: lm.loss_fn(p, {"tokens": tokens}, prog_cfg, remat="none"),
            has_aux=True)(p0)
        ref = minicpm.Reference(dm, cfg["optimizer"], head_chunk=8)
        params = minicpm.Reference.from_program_layout(p0)
        got = {}

        def on_block(i, g):
            for part, leaves in g.items():
                for name, v in leaves.items():
                    got.setdefault(f"blocks.{part}.{name}", {})[i] = v

        ref_loss, g_norm, g_embed = ref.loss_and_grads(params, tokens, on_block)
    assert ref_loss == pytest.approx(float(loss), rel=1e-5)
    np.testing.assert_allclose(g_embed, grads["embed"], rtol=2e-4, atol=1e-7)
    np.testing.assert_allclose(g_norm, grads["final_norm"], rtol=2e-4, atol=1e-7)
    for path, per_layer in got.items():
        _, part, name = path.split(".")
        stacked = jnp.stack([per_layer[i] for i in sorted(per_layer)])
        np.testing.assert_allclose(stacked, grads["blocks"][part][name],
                                   rtol=2e-4, atol=1e-7)


def test_reference_adamw_matches_the_program_optimizer():
    from bench.runners import train as train_runner

    cfg = _tiny()
    opt = train_runner.program_optimizer(dict(cfg["optimizer"], master_fp32=False))
    ref = minicpm.Reference(minicpm.dims(cfg), cfg["optimizer"])
    p = {"w": jnp.linspace(-1.0, 1.0, 12, dtype=jnp.float32)}
    g = {"w": jnp.cos(jnp.arange(12, dtype=jnp.float32))}
    state = opt.init(p)
    pp, mm, vv = p["w"], jnp.zeros(12), jnp.zeros(12)
    for step in range(3):
        p, state = opt.update(g, state, p, jnp.int32(step))
        pp, mm, vv = ref._adam_leaf(g["w"], pp, mm, vv, ref.lr(step), float(step + 1))
    np.testing.assert_allclose(pp, p["w"], rtol=1e-6, atol=1e-9)


def test_pricing_reference_matches_the_program_evaluator():
    from bench.runners import admit

    from .helpers import small_cell

    cell = small_cell("admit.shared432.churn")
    cfg = cell.config
    hw, policy, jobs = admit.program_inputs(cfg)
    from repro.core.alternating import co_optimize_jobset
    from repro.core.strategy_search import evaluate_jobset
    from repro.core.workloads import JobSet, TenantJob

    names = ["dlrm", "bert", "candle"]
    js = JobSet(n=cfg["servers"], tenants=[
        TenantJob(spec=jobs[n], name=f"t{i}",
                  servers=tuple(range(i * 8, i * 8 + 8)))
        for i, n in enumerate(names)])
    plan = co_optimize_jobset(js, hw, rounds=2, mcmc_iters=30, seed=4)
    want, _, _ = evaluate_jobset(plan.strategies, js, plan.topology, hw)
    got = admit.price(cfg, plan, js)
    assert got == pytest.approx(want, rel=1e-12)
    assert pricing.plan_violations(
        admit.tenant_views(cfg, js, plan.strategies),
        admit.plan_parts(plan)[0], cfg["degree"], cfg["servers"]) == []
    hybrid = [s for s in plan.strategies.values() if s.mode == "hybrid"]
    modes = {s.mode for s in plan.strategies.values()}
    assert modes <= {"dp", "hybrid"} and (hybrid or modes == {"dp"})
