"""Mamba-1 selective scan as a Pallas TPU kernel.

TPU adaptation (not a port of the CUDA kernel): grid = (batch, d_inner
blocks, time chunks) with the chunk axis innermost and sequential — the SSM
state h (block_d, d_state) persists in VMEM scratch across chunk grid steps,
so the (B, L, D, N) decay/drive tensors are never materialized in HBM (the
XLA fallback in models/layers.py materializes them per chunk).  Inputs are
streamed HBM->VMEM per (chunk, d-block); the inner time loop is VPU work
over (block_d, d_state) registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(xc_ref, dt_ref, a_ref, b_ref, c_ref, dskip_ref,
                 y_ref, hout_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...]  # (blk, ST) — A matrix (negative)
    dskip = dskip_ref[0]  # (blk,)

    def step(t, h):
        x_t = xc_ref[0, t, :].astype(jnp.float32)  # (blk,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)  # (blk,)
        bv = b_ref[0, t, :].astype(jnp.float32)  # (ST,)
        cv = c_ref[0, t, :].astype(jnp.float32)  # (ST,)
        decay = jnp.exp(dt_t[:, None] * a)  # (blk, ST)
        drive = (dt_t * x_t)[:, None] * bv[None, :]
        h = decay * h + drive
        y_t = jnp.sum(h * cv[None, :], axis=1) + dskip * x_t
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0] = h


@functools.partial(
    jax.jit, static_argnames=("block_d", "chunk", "interpret")
)
def mamba_scan(
    xc: jax.Array,  # (B, L, DI) post-conv activations
    dt: jax.Array,  # (B, L, DI) fp32 softplus'd step sizes
    a: jax.Array,  # (DI, ST) negative state matrix
    b: jax.Array,  # (B, L, ST)
    c: jax.Array,  # (B, L, ST)
    d_skip: jax.Array,  # (DI,)
    block_d: int = 512,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (y (B, L, DI) fp32, h_final (B, DI, ST) fp32)."""
    B, L, DI = xc.shape
    ST = a.shape[1]
    block_d = min(block_d, DI)
    chunk = min(chunk, L)
    assert DI % block_d == 0 and L % chunk == 0
    grid = (B, DI // block_d, L // chunk)
    # The time loop reads and writes one row per step at a dynamic offset,
    # which Mosaic lowers for 32-bit data only.
    xc, dt, b, c = (v.astype(jnp.float32) for v in (xc, dt, b, c))

    kernel = functools.partial(_scan_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((block_d, ST), lambda bi, di, ci: (di, 0)),
            pl.BlockSpec((1, chunk, ST), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, ST), lambda bi, di, ci: (bi, ci, 0)),
            pl.BlockSpec((1, block_d), lambda bi, di, ci: (0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, block_d, ST), lambda bi, di, ci: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, DI), jnp.float32),
            jax.ShapeDtypeStruct((B, DI, ST), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, ST), jnp.float32)],
        interpret=interpret,
    )(xc, dt, a, b, c, d_skip[None, :])
