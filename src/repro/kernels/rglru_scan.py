"""RG-LRU linear-recurrence scan (Griffin) as a Pallas TPU kernel.

h_t = a_t * h_{t-1} + b_t, elementwise over the channel dim.  Grid =
(batch, channel blocks, time chunks), chunk axis innermost/sequential with
the carry in VMEM scratch — identical scheduling to the Mamba kernel but a
pure VPU elementwise recurrence (no state dim).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lru_kernel(a_ref, b_ref, y_ref, hout_ref, h_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        h = a_ref[0, t, :].astype(jnp.float32) * h + b_ref[0, t, :].astype(
            jnp.float32
        )
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])

    @pl.when(ci == nc - 1)
    def _finish():
        hout_ref[0, 0] = h_scr[...]


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def rglru_scan(
    a: jax.Array,  # (B, L, D) decay in (0, 1)
    b: jax.Array,  # (B, L, D) gated drive
    block_d: int = 512,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (h_all (B, L, D) fp32, h_final (B, D) fp32)."""
    B, L, D = a.shape
    block_d = min(block_d, D)
    chunk = min(chunk, L)
    assert D % block_d == 0 and L % chunk == 0
    grid = (B, D // block_d, L // chunk)
    # The time loop reads and writes one row per step at a dynamic offset,
    # which Mosaic lowers for 32-bit data only.
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)

    kernel = functools.partial(_lru_kernel, chunk=chunk)
    h_all, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, di, ci: (bi, ci, di)),
            # (1, 1, block_d): a (1, block_d) block of a (B, D) array
            # would break the TPU's 8-row tiling for B > 1.
            pl.BlockSpec((1, 1, block_d), lambda bi, di, ci: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, L, D), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d,), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return h_all, h_fin[:, 0]
