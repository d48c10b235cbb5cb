"""JAX-native TotientPerms collectives (§6 "Modifications to NCCL").

The paper integrates TotientPerms into NCCL so parameter synchronization is
load-balanced across several ring-AllReduce permutations.  Here we implement
the same idea with :func:`jax.lax.ppermute` inside ``shard_map``:

* ``ring_all_reduce(x, axis, p)`` — bandwidth-optimal ring AllReduce over the
  stride-``p`` regular ring (reduce-scatter + all-gather, n-1 steps each).
* ``multi_ring_all_reduce(x, axis, strides)`` — split ``x`` into
  ``len(strides)`` chunks, each reduced around a *different* TotientPerms
  ring.  On a TPU torus each stride lands on a distinct ICI direction, so the
  chunks genuinely move in parallel — the degree-``d`` bandwidth of the paper.

All variants are bit-comparable to ``lax.psum`` (tests assert allclose; exact
for integer inputs).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# TPU vector lane width: blocks of this many columns tile without padding.
_LANES = 128


def _mod_inverse(p: int, n: int) -> int:
    if math.gcd(p, n) != 1:
        raise ValueError(f"stride {p} not coprime with ring size {n}")
    return pow(p, -1, n)


def _ring_perm(n: int, p: int) -> list[tuple[int, int]]:
    """ppermute pairs: device i sends to (i + p) mod n."""
    return [(i, (i + p) % n) for i in range(n)]


def _blocks(x: jax.Array, k: int) -> jax.Array:
    """``x`` zero-padded and cut into ``k`` equal 2-D chunks,
    ``(k, rows, cols)``, for the collectives to move.

    Whole rows of the last axis where there are at least 8 per chunk:
    slicing rows keeps the TPU's (8, 128)-tiled layout, so no relayout
    copy meets a collective.  Otherwise the flattened values in blocks of
    128 columns.  The TPU compiler takes time in proportion to the length
    of a permuted 1-D slice, and to the size of a relayout feeding
    several trees (minutes for an embedding-sized gradient)."""
    if x.ndim >= 2 and x.size // x.shape[-1] >= 8 * k:
        m = x.reshape(-1, x.shape[-1])
        rows = -(-m.shape[0] // (8 * k)) * 8
        pad = k * rows - m.shape[0]
        if pad:
            m = jnp.pad(m, ((0, pad), (0, 0)))
        return m.reshape(k, rows, m.shape[1])
    flat = x.reshape(-1)
    rows = -(-flat.size // (k * _LANES))
    pad = k * rows * _LANES - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(k, rows, _LANES)


def _unblocks(blocks: jax.Array, like: jax.Array) -> jax.Array:
    """Inverse of :func:`_blocks`: drop the padding, restore the shape."""
    if like.ndim >= 2 and blocks.shape[-1] == like.shape[-1]:
        cols = like.shape[-1]
        return blocks.reshape(-1, cols)[: like.size // cols].reshape(like.shape)
    return blocks.reshape(-1)[: like.size].reshape(like.shape)


def _ring_rows(acc: jax.Array, axis_name: str, p: int) -> jax.Array:
    """Ring AllReduce of ``acc`` (n, ...) whose leading axis holds the n
    segments, over the stride-``p`` permutation of ``axis_name``."""
    n = acc.shape[0]
    inv_p = _mod_inverse(p, n)
    perm = _ring_perm(n, p)
    # Position of this device along the ring: ring visits (j * p) % n.
    pos = (lax.axis_index(axis_name) * inv_p) % n

    def seg_at(arr, idx):
        return lax.dynamic_index_in_dim(arr, idx % n, axis=0, keepdims=False)

    # Reduce-scatter: after n-1 steps, position j owns segment (j + 1) % n.
    for t in range(n - 1):
        send_idx = (pos - t) % n
        recv_idx = (pos - t - 1) % n
        sent = seg_at(acc, send_idx)
        received = lax.ppermute(sent, axis_name, perm)
        acc = lax.dynamic_update_index_in_dim(
            acc, seg_at(acc, recv_idx) + received, recv_idx % n, axis=0
        )

    # All-gather the reduced segments back around the same ring.
    for t in range(n - 1):
        send_idx = (pos + 1 - t) % n
        recv_idx = (pos - t) % n
        sent = seg_at(acc, send_idx)
        received = lax.ppermute(sent, axis_name, perm)
        acc = lax.dynamic_update_index_in_dim(acc, received, recv_idx % n, axis=0)
    return acc


def ring_all_reduce(x: jax.Array, axis_name: str, p: int = 1) -> jax.Array:
    """Ring AllReduce over the stride-``p`` permutation of ``axis_name``.

    Must be called inside ``shard_map``.  Equivalent to ``lax.psum(x, axis)``.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    return _unblocks(_ring_rows(_blocks(x, n), axis_name, p), x)


def ring_reduce_scatter(x: jax.Array, axis_name: str, p: int = 1) -> jax.Array:
    """Reduce-scatter over the stride-``p`` ring: input logically
    (n * chunk,) flattened; returns this device's reduced chunk, ordered so
    that ``ring_all_gather`` reassembles ``psum(x)``.  Device at ring position
    j returns segment (j+1) % n mapped back to device order."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x.reshape(-1)
    inv_p = _mod_inverse(p, n)
    perm = _ring_perm(n, p)
    pos = (lax.axis_index(axis_name) * inv_p) % n

    flat = x.reshape(-1)
    seg = -(-flat.size // n)
    pad = seg * n - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    acc = flat.reshape(n, seg)

    def seg_at(arr, idx):
        return lax.dynamic_index_in_dim(arr, idx % n, axis=0, keepdims=False)

    for t in range(n - 1):
        send_idx = (pos - t) % n
        recv_idx = (pos - t - 1) % n
        received = lax.ppermute(seg_at(acc, send_idx), axis_name, perm)
        acc = lax.dynamic_update_index_in_dim(
            acc, seg_at(acc, recv_idx) + received, recv_idx % n, axis=0
        )
    # Owned segment index: (pos + 1) % n.
    return seg_at(acc, (pos + 1) % n)


def multi_ring_all_reduce(
    x: jax.Array, axis_name: str, strides: tuple[int, ...] | list[int]
) -> jax.Array:
    """AllReduce load-balanced over several TotientPerms rings (§6).

    ``x`` is split into ``len(strides)`` equal chunks; chunk r is reduced
    around the stride ``strides[r]`` ring.  All chunk reductions are
    independent programs, so XLA's latency-hiding scheduler can run them
    concurrently over distinct ICI links.
    """
    strides = tuple(strides)
    r = len(strides)
    if r == 0:
        raise ValueError("need at least one ring stride")
    if r == 1:
        return ring_all_reduce(x, axis_name, strides[0])

    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    blocks = _blocks(x, r * n)
    chunks = blocks.reshape(r, n, *blocks.shape[1:])
    reduced = [
        _ring_rows(chunks[i], axis_name, strides[i]) for i in range(r)
    ]
    return _unblocks(jnp.stack(reduced), x)


def recursive_hd_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Recursive halving-doubling AllReduce (the latency-optimal schedule of
    :mod:`repro.core.schedules`): ``log2(n)`` halving exchanges
    (reduce-scatter with partner ``i XOR d``) followed by ``log2(n)``
    doubling exchanges (all-gather), ``2 log2(n)`` ppermute rounds total vs
    the ring's ``2 (n-1)``.  Power-of-two groups only — the demand compiler
    folds stragglers into the core, the runtime kernel keeps the strict
    form.  Equivalent to ``lax.psum(x, axis)`` (exact for integer inputs:
    every addition is a disjoint pairwise tree).
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    if n < 2 or n & (n - 1):
        raise ValueError(
            f"recursive halving-doubling needs a power-of-two group, got {n}"
        )
    me = lax.axis_index(axis_name)

    acc = _blocks(x, n)

    # Recursive halving: the live block [lo, lo + 2d) splits at each round;
    # the kept half accumulates the partner's complementary half.
    lo = jnp.zeros_like(me)
    d = n // 2
    while d >= 1:
        bit = (me >> (d.bit_length() - 1)) & 1
        keep_lo = lo + bit * d
        send_lo = lo + (1 - bit) * d
        perm = [(i, i ^ d) for i in range(n)]
        sent = lax.dynamic_slice_in_dim(acc, send_lo, d, axis=0)
        received = lax.ppermute(sent, axis_name, perm)
        kept = lax.dynamic_slice_in_dim(acc, keep_lo, d, axis=0)
        acc = lax.dynamic_update_slice_in_dim(
            acc, kept + received, keep_lo, axis=0
        )
        lo = keep_lo
        d //= 2
    # Device i now owns fully-reduced segment i (lo == me by construction).
    # Recursive doubling: exchange ever-larger aligned blocks back.
    d = 1
    while d < n:
        perm = [(i, i ^ d) for i in range(n)]
        sent = lax.dynamic_slice_in_dim(acc, lo, d, axis=0)
        received = lax.ppermute(sent, axis_name, perm)
        acc = lax.dynamic_update_slice_in_dim(acc, received, lo ^ d, axis=0)
        lo = jnp.minimum(lo, lo ^ d)
        d *= 2

    return _unblocks(acc, x)


def _tree_all_reduce(x: jax.Array, axis_name: str, order: list[int]) -> jax.Array:
    """AllReduce over one balanced binary tree: heap node ``i`` (device
    ``order[i]``) parents ``order[(i-1)//2]``.  Reduce runs deepest level
    first (left/right children in separate ppermute rounds — a parent has
    one source per round), then the root's total broadcasts back down."""
    n = len(order)
    me = lax.axis_index(axis_name)
    # Heap indices grouped by depth: [1,2], [3..6], [7..14], ...
    levels: list[list[int]] = []
    start, width = 1, 2
    while start < n:
        levels.append(list(range(start, min(start + width, n))))
        start += width
        width *= 2
    acc = x
    for level in reversed(levels):
        for parity in (1, 0):  # left children first, then right
            pairs = [
                (order[i], order[(i - 1) // 2])
                for i in level
                if i % 2 == parity
            ]
            if not pairs:
                continue
            # Non-recipients get zeros from ppermute, so a plain add only
            # touches the parents.
            acc = acc + lax.ppermute(acc, axis_name, pairs)
    for level in levels:
        for parity in (1, 0):
            pairs = [
                (order[(i - 1) // 2], order[i])
                for i in level
                if i % 2 == parity
            ]
            if not pairs:
                continue
            received = lax.ppermute(acc, axis_name, pairs)
            mask = jnp.zeros((), dtype=bool)
            for _, dst in pairs:
                mask = mask | (me == dst)
            acc = jnp.where(mask, received, acc)
    return acc


def multi_tree_all_reduce(
    x: jax.Array, axis_name: str, strides: tuple[int, ...] | list[int]
) -> jax.Array:
    """AllReduce load-balanced over several balanced binary trees, one per
    TotientPerms ring order (the ``multi_tree`` schedule of
    :mod:`repro.core.schedules`): ``x`` splits into ``len(strides)`` chunks
    and chunk ``r`` reduces up / broadcasts down the tree laid over the
    stride ``strides[r]`` ring order.  ``2 floor(log2(n))`` serial rounds
    per tree; the trees are independent programs over (mostly) disjoint
    edges, so they overlap.  Equivalent to ``lax.psum`` (exact for integer
    inputs)."""
    strides = tuple(strides)
    r = len(strides)
    if r == 0:
        raise ValueError("need at least one tree stride")
    from .totient import ring_order

    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    orders = [[int(v) for v in ring_order(n, p)] for p in strides]

    chunks = _blocks(x, r)
    reduced = [
        _tree_all_reduce(chunks[i], axis_name, orders[i]) for i in range(r)
    ]
    return _unblocks(jnp.stack(reduced), x)


def topoopt_psum_fn(
    strides: tuple[int, ...] | None,
    axis_name: str,
    schedule: str = "ring",
    group_size: int | None = None,
):
    """The gradient-sync collective a training step should use, selected from
    the searched :class:`~repro.core.strategy_search.Strategy` ``schedule``
    (all three kernels are ``lax.psum``-equivalent):

    * ``"ring"`` — multi-ring TotientPerms AllReduce when a TopoOpt plan
      supplies strides, otherwise plain ``lax.psum`` (single XLA all-reduce).
    * ``"recursive_hd"`` — recursive halving-doubling.  The strict runtime
      kernel needs a power-of-two group, so when ``group_size`` is known and
      is not one, selection falls back to the ring family — the same fold
      the demand compiler applies to straggler nodes.
    * ``"multi_tree"`` — balanced binary trees seeded from the TotientPerms
      ring orders; without strides there is no tree seed and plain
      ``lax.psum`` is used.
    """
    if schedule == "recursive_hd":
        if group_size is None or (group_size & (group_size - 1)) == 0:
            return partial(recursive_hd_all_reduce, axis_name=axis_name)
        schedule = "ring"  # straggler fold: non-pow2 groups keep ringing
    elif schedule == "multi_tree":
        if strides:
            return partial(
                multi_tree_all_reduce, axis_name=axis_name,
                strides=tuple(strides),
            )
        return partial(lax.psum, axis_name=axis_name)
    elif schedule != "ring":
        raise ValueError(
            f"unknown collective schedule {schedule!r}: "
            "expected 'ring', 'recursive_hd' or 'multi_tree'"
        )
    if strides:
        return partial(multi_ring_all_reduce, axis_name=axis_name, strides=tuple(strides))
    return partial(lax.psum, axis_name=axis_name)


def all_to_all_ring(x: jax.Array, axis_name: str, p: int = 1) -> jax.Array:
    """All-to-all (MoE dispatch pattern) implemented as n-1 ppermute rotations
    around a stride-``p`` ring — the host-based-forwarding analogue for EP
    traffic on a direct-connect fabric.  ``x``: (n, ...) per-destination data;
    returns (n, ...) per-source data.  Equivalent to lax.all_to_all."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    me = lax.axis_index(axis_name)
    out = jnp.zeros_like(x)
    out = lax.dynamic_update_index_in_dim(
        out, lax.dynamic_index_in_dim(x, me, 0, keepdims=False), me, axis=0
    )
    # Rotate the full payload around the ring; at each step keep the slice
    # destined to us.  Bandwidth-suboptimal vs switch all-to-all by the
    # average-hop factor — exactly the paper's bandwidth tax (§5.4).
    perm = _ring_perm(n, p)
    payload = x
    src = me
    for _ in range(n - 1):
        payload = lax.ppermute(payload, axis_name, perm)
        src = (src - p) % n
        mine = lax.dynamic_index_in_dim(payload, me, 0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(out, mine, src, axis=0)
    return out
