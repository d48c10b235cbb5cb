"""Int8 gradient compression with error feedback.

The wire payload of the ring AllReduce is int8 + per-block fp32 scales (4x
less traffic than fp32, 2x less than bf16 — directly visible in the HLO
collective bytes of the dry-run).  Quantization errors are accumulated into a
local residual and re-injected on the next step (error feedback), which keeps
SGD convergence (Karimireddy et al., EF-signSGD).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ..core.collectives import _mod_inverse, _ring_perm


def quantize_block(x, block: int = 1024):
    """x: flat fp array -> (int8 codes, fp32 scales (nb,), padded_len)."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-20)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0], flat.size


def dequantize_block(q, scale):
    return (q.astype(jnp.float32) * scale[:, None]).reshape(-1)


def compressed_ring_all_reduce(
    x: jax.Array, axis_name: str, p: int = 1, block: int = 1024
):
    """Ring AllReduce whose every hop carries int8 codes + scales.

    Per-hop requantization error is kept locally and returned as a residual
    with x's shape.  Returns (allreduced_approx, residual)."""
    n = lax.axis_size(axis_name)
    shape = x.shape
    if n == 1:
        return x, jnp.zeros_like(x)
    inv_p = _mod_inverse(p, n)
    perm = _ring_perm(n, p)
    pos = (lax.axis_index(axis_name) * inv_p) % n

    flat = x.reshape(-1).astype(jnp.float32)
    seg = -(-flat.size // n)
    seg = -(-seg // block) * block  # segment multiple of block
    pad = seg * n - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    acc = flat.reshape(n, seg)
    err = jnp.zeros_like(acc)

    def seg_at(arr, idx):
        return lax.dynamic_index_in_dim(arr, idx % n, axis=0, keepdims=False)

    # Reduce-scatter with per-hop quantization.
    for t in range(n - 1):
        send_idx = (pos - t) % n
        recv_idx = (pos - t - 1) % n
        payload = seg_at(acc, send_idx)
        q, s, _ = quantize_block(payload, block)
        deq = dequantize_block(q, s)[: payload.size]
        err = lax.dynamic_update_index_in_dim(
            err, seg_at(err, send_idx) + (payload - deq), send_idx % n, axis=0
        )
        rq = lax.ppermute(q, axis_name, perm)
        rs = lax.ppermute(s, axis_name, perm)
        received = dequantize_block(rq, rs)[: payload.size]
        acc = lax.dynamic_update_index_in_dim(
            acc, seg_at(acc, recv_idx) + received, recv_idx % n, axis=0
        )

    # All-gather phase: quantize the reduced segment once, rotate int8.
    own_idx = (pos + 1) % n
    own = seg_at(acc, own_idx)
    q, s, _ = quantize_block(own, block)
    deq = dequantize_block(q, s)[: own.size]
    err = lax.dynamic_update_index_in_dim(
        err, seg_at(err, own_idx) + (own - deq), own_idx % n, axis=0
    )
    acc = lax.dynamic_update_index_in_dim(acc, deq, own_idx % n, axis=0)
    for t in range(n - 1):
        send_idx = (pos + 1 - t) % n
        recv_idx = (pos - t) % n
        payload = seg_at(acc, send_idx)
        q, s, _ = quantize_block(payload, block)
        rq = lax.ppermute(q, axis_name, perm)
        rs = lax.ppermute(s, axis_name, perm)
        received = dequantize_block(rq, rs)[: payload.size]
        acc = lax.dynamic_update_index_in_dim(acc, received, recv_idx % n, axis=0)

    out = acc.reshape(-1)[: flat.size - pad if pad else flat.size]
    res = err.reshape(-1)[: flat.size - pad if pad else flat.size]
    return out.reshape(shape).astype(x.dtype), res.reshape(shape).astype(jnp.float32)


@dataclass(frozen=True)
class Compressor:
    block: int = 1024

    def init_residual(self, params):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def sync(self, grads, residual, axis_name: str, strides=(1,)):
        """Error-feedback compressed gradient sync.  Returns
        (mean_grads, new_residual)."""
        n = lax.axis_size(axis_name)
        strides = tuple(strides) or (1,)
        leaves, treedef = jax.tree.flatten(grads)
        res_leaves = treedef.flatten_up_to(residual)
        outs, new_res = [], []
        for i, (g, r) in enumerate(zip(leaves, res_leaves)):
            p = strides[i % len(strides)]
            g_fed = g.astype(jnp.float32) + r
            summed, err = compressed_ring_all_reduce(
                g_fed, axis_name, p=p, block=self.block
            )
            outs.append((summed / n).astype(g.dtype))
            new_res.append(err)
        return jax.tree.unflatten(treedef, outs), jax.tree.unflatten(treedef, new_res)
