"""Compile the main path's device programs for one TPU v5e chip.

The chip is described, not attached: XLA's TPU compiler runs here and
refuses what the chip would refuse (tile alignment, fast-memory and HBM
budgets), at the real widths of registered configs.  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import chip_smoke
from repro.configs.base import ShapeSpec, get_config, input_specs
from repro.core.planeval_jax import DEFAULT_TEMPER_LADDER, _grid_program, _require_jax
from repro.core.workloads import DLRM
from repro.models import layers
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rglru_scan import rglru_scan
from repro.optim import adamw, wsd
from repro.parallel.sharding import ShardingPlan
from repro.train.steps import jit_train_step

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _specs(one_chip, *shapes):
    return [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]


def _fused_fwd(q, k, v):
    return ops.fused_attention(q, k, v, causal=True, interpret=False)


def _fused_loss(q, k, v):
    return jnp.sum(_fused_fwd(q, k, v).astype(jnp.float32))


def _kernel_cases():
    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    lm = get_config("minicpm-2b")
    moe = get_config("qwen3-moe-30b-a3b")
    ssm = get_config("falcon-mamba-7b")
    rec = get_config("recurrentgemma-9b")
    q = (1, lm.n_heads, 2048, lm.hd)
    kv = (1, lm.n_kv_heads, 2048, lm.hd)
    di, st = ssm.d_inner, ssm.ssm_state
    # DLRM tables at the paper's count and width; 1e5 of the 1e7 rows per
    # table (a chip holds 16 GB, the paper's tables 327 GB).
    tables = (DLRM.n_tables, 100_000, DLRM.table_dim)
    bags = (DLRM.batch_per_gpu, DLRM.n_tables, 4)
    # The training cell's attention: batch 2 of 1024 tokens, 36 heads of
    # 64, the sequence minor.
    cell = (2, lm.n_heads, lm.hd, 1024)
    return {
        "flash_attention-minicpm-2b": (
            flash_attention, [(q, bf16), (kv, bf16), (kv, bf16)]),
        "fused_attention-fwd-minicpm-2b": (
            jax.jit(_fused_fwd), [(cell, bf16)] * 3),
        "fused_attention-bwd-minicpm-2b": (
            jax.jit(jax.grad(_fused_loss, argnums=(0, 1, 2))), [(cell, bf16)] * 3),
        "moe_gmm-qwen3-moe-30b-a3b": (
            moe_gmm, [((moe.n_experts, 256, moe.d_model), bf16),
                      ((moe.n_experts, moe.d_model, moe.d_ff), bf16)]),
        "embedding_bag-dlrm-paper": (
            embedding_bag, [(tables, f32), (bags, i32)]),
        "mamba_scan-falcon-mamba-7b": (
            mamba_scan, [((2, 1024, di), bf16), ((2, 1024, di), f32),
                         ((di, st), f32), ((2, 1024, st), f32),
                         ((2, 1024, st), f32), ((di,), f32)]),
        "rglru_scan-recurrentgemma-9b": (
            rglru_scan, [((2, 1024, rec.d_inner), bf16),
                         ((2, 1024, rec.d_inner), bf16)]),
    }


@pytest.mark.parametrize("case", sorted(_kernel_cases()))
def test_pallas_kernel_compiles_for_v5e(one_chip, case):
    kernel, shapes = _kernel_cases()[case]
    # The kernels run in training steps, which trace with x64 off; a test
    # run earlier in this process may have turned it on.
    with jax.enable_x64(False):
        compiled = kernel.lower(*_specs(one_chip, *shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_admission_grid_compiles_for_v5e(one_chip):
    """The fused candidate x chain x rung grid at an upper bound of
    chip_smoke's Phase B shapes: 4 candidates, 14 tenants, a 64-strategy
    pool and all 3456 directed links of the 432-server degree-8 fabric (a
    run packs only the links its demand loads), 40 annealing steps."""
    jnp64 = _require_jax().numpy
    C, K, M = 4, 4, len(DEFAULT_TEMPER_LADDER)
    T, S, iters = 14, 64, 40
    L = chip_smoke.CLUSTER["n"] * chip_smoke.CLUSTER["degree"]
    f, i = jnp64.float64, jnp64.int64
    args = _specs(
        one_chip,
        ((C, T, S, L), f), ((C, L), f), ((T,), f), ((T,), f),
    ) + [None] + _specs(
        one_chip,
        ((C, T), i), ((M,), f), ((C, K, M, iters), i), ((C, K, M, iters), i),
        ((C, K, M, iters), f), ((C, K, iters, M // 2), f), ((iters,), i),
    )
    fn = _grid_program("union", 0.0, 0.0, float(T), False)
    compiled = fn.lower(*args).compile()
    used = compiled.memory_analysis()
    assert used.argument_size_in_bytes + used.temp_size_in_bytes < V5E_HBM


def test_train_step_fits_one_chip(topo, one_chip):
    """Phase A's depth cut: the minicpm-2b train step at chip_smoke's depth
    compiles for one chip and leaves 1.5 GiB of its HBM free."""
    base = get_config(chip_smoke.MODEL)
    cfg = dataclasses.replace(base, n_layers=chip_smoke.TRAIN_LAYERS)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    jitted, (p_specs, o_specs, p_sh, o_sh, _) = jit_train_step(
        cfg, adamw(wsd(3e-3, 5)), ShardingPlan(fsdp=False, remat="full"), mesh
    )

    def place(tree, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings,
        )

    shape = ShapeSpec("smoke", chip_smoke.TRAIN_SEQ, chip_smoke.TRAIN_BATCH,
                      "train")
    batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        input_specs(cfg, shape),
    )
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    # Phase A traces before the planner turns on x64 for the process.
    with jax.enable_x64(False):
        compiled = jitted.lower(
            place(p_specs, p_sh), place(o_specs, o_sh), batch, step
        ).compile()
    used = compiled.memory_analysis()
    total = used.argument_size_in_bytes + used.temp_size_in_bytes
    assert total <= V5E_HBM - 1.5 * 2**30, total / 2**30


def test_train_step_with_fused_attention_fits_one_chip(topo, one_chip,
                                                       monkeypatch):
    """The same step as the chip runs it: attention through the fused
    kernels (the choice of path sees this process's CPU, so the test takes
    the TPU's branch itself).  It compiles, holds the kernels, and fits as
    the XLA path does."""
    monkeypatch.setattr(layers, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    base = get_config(chip_smoke.MODEL)
    cfg = dataclasses.replace(base, n_layers=chip_smoke.TRAIN_LAYERS)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    jitted, (p_specs, o_specs, p_sh, o_sh, _) = jit_train_step(
        cfg, adamw(wsd(3e-3, 5)), ShardingPlan(fsdp=False, remat="full"), mesh
    )

    def place(tree, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings,
        )

    shape = ShapeSpec("smoke", chip_smoke.TRAIN_SEQ, chip_smoke.TRAIN_BATCH,
                      "train")
    batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        input_specs(cfg, shape),
    )
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    with jax.enable_x64(False):
        compiled = jitted.lower(
            place(p_specs, p_sh), place(o_specs, o_sh), batch, step
        ).compile()
    # the forward kernel in the forward and in the remat of the backward,
    # and the backward kernel
    assert compiled.as_text().count("tpu_custom_call") >= 3
    used = compiled.memory_analysis()
    total = used.argument_size_in_bytes + used.temp_size_in_bytes
    assert total <= V5E_HBM - 1.5 * 2**30, total / 2**30
