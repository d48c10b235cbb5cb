"""DLRM embedding-bag lookup as a Pallas TPU kernel.

out[b, t] = sum_j table[t, idx[b, t, j]] — multi-hot embedding-bag over T
tables.  TPU-native design: indices are *scalar-prefetched*
(PrefetchScalarGridSpec) so the BlockSpec index_map itself selects the table
row to DMA per grid step — the gather is expressed as data-dependent block
fetches, the canonical TPU pattern for embedding lookups (no scatter/gather
unit on TPU).  Accumulation over the NNZ axis happens in the revisited
output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bag_kernel(idx_ref, row_ref, o_ref, *, rows: int, n_tables: int,
                nnz: int):
    b, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((t == 0) & (j == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # The fetched block holds ``rows`` table rows; pick the indexed one.
    r = idx_ref[(b * n_tables + t) * nnz + j] % rows
    o_ref[0, pl.ds(t, 1), :] += row_ref[0, pl.ds(r, 1), :].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag(
    tables: jax.Array,  # (T, R, E) stacked embedding tables
    indices: jax.Array,  # (B, T, NNZ) int32 row ids
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, T, E) bag sums."""
    T, R, E = tables.shape
    B, T2, NNZ = indices.shape
    assert T == T2

    # TPU blocks tile the last two dims by (8, 128) unless a block spans
    # the whole dim: fetch the aligned group of ``rows`` table rows that
    # holds the indexed row, and keep one (T, E) output block per batch
    # row resident while its tables and bag entries accumulate into it.
    # The indices are prefetched flat: SMEM pads each trailing dim of a
    # multi-dim operand, which would overflow it at DLRM batch sizes.
    rows = min(8, R)

    def table_map(b, t, j, idx_ref):
        return (t, idx_ref[(b * T + t) * NNZ + j] // rows, 0)

    def out_map(b, t, j, idx_ref):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, T, NNZ),
        in_specs=[pl.BlockSpec((1, rows, E), table_map)],
        out_specs=pl.BlockSpec((1, T, E), out_map),
    )
    # Accumulate in fp32 regardless of table dtype (the revisited output
    # block is the accumulator, so its dtype is the accumulation dtype).
    acc_dtype = jnp.promote_types(tables.dtype, jnp.float32)
    out = pl.pallas_call(
        functools.partial(_bag_kernel, rows=rows, n_tables=T, nnz=NNZ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, E), acc_dtype),
        interpret=interpret,
    )(indices.reshape(-1), tables)
    return out.astype(tables.dtype)
