"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True on the CPU (the test suite) and False on the
TPU, where the kernels compile to Mosaic; any other backend raises, since
the kernels have no lowering there.  The XLA fallbacks live in
models/layers.py; these wrappers are the TPU fast path.
"""

from __future__ import annotations

import math

import jax
from jax.experimental.pallas.ops.tpu.splash_attention import (
    BlockSizes,
    CausalMask,
    FullMask,
    LocalMask,
    MultiHeadMask,
    QKVLayout,
    make_splash_mha,
)

from .embedding_bag import embedding_bag
from .flash_attention import flash_attention
from .moe_gmm import moe_gmm
from .mamba_scan import mamba_scan
from .rglru_scan import rglru_scan


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas TPU kernels have no lowering for backend {backend!r}"
        )
    return backend == "cpu"


def attention(q, k, v, causal=True, window=0, **kw):
    kw.setdefault("interpret", _default_interpret())
    return flash_attention(q, k, v, causal=causal, window=window, **kw)


# The fused attention tiles the sequence in blocks of SEQ_BLOCK; a sequence
# that is not a multiple of it takes the XLA path (models/layers.attention).
SEQ_BLOCK = 128
# Largest block of either kernel: the fastest forward+backward on a TPU v5e
# at (B 2, H 36, S 1024, d 64) in bf16 among blocks of 128 to 1024 (PERF.md).
_MAX_BLOCK = 512


def _splash_kernel(heads: int, seq: int, causal: bool, window: int,
                   interpret: bool):
    if window:
        # query i sees keys i - window + 1 .. i, as layers.causal_mask
        mask = LocalMask((seq, seq), (window - 1, 0), offset=0)
    elif causal:
        mask = CausalMask((seq, seq))
    else:
        mask = FullMask((seq, seq))
    b = math.gcd(seq, _MAX_BLOCK)
    # One backward kernel gives dq with dk and dv.  q, k and v lie with the
    # sequence minor in memory.
    minor = QKVLayout.SEQ_MINOR
    blocks = BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
                        use_fused_bwd_kernel=True, q_layout=minor,
                        k_layout=minor, v_layout=minor)
    return make_splash_mha(MultiHeadMask([mask] * heads), block_sizes=blocks,
                           head_shards=1, q_seq_shards=1, interpret=interpret)


def fused_attention(q, k, v, *, causal=True, window=0, interpret=None):
    """Self-attention with the scores kept on chip, and a fused backward
    (the shipped splash attention kernels).  Blocks wholly masked out, as
    above the diagonal of causal attention, are skipped.

    q: (B, H, D, S), the sequence minor, already scaled by 1/sqrt(D);
    k, v: (B, KV, D, S) with H a multiple of KV (query head h reads key
    head h // (H // KV)); S a multiple of :data:`SEQ_BLOCK`.  ``window``
    > 0 (causal only) lets query i see keys i - window + 1 .. i.  Scores,
    softmax and accumulation are float32; the forward's probability-value
    product is float32 too, and every other matmul takes inputs of q's
    dtype.  Returns (B, H, S, D).
    """
    if interpret is None:
        interpret = _default_interpret()
    if window and not causal:
        raise ValueError("a window is causal here")
    kernel = _splash_kernel(q.shape[1], q.shape[3], causal, window, interpret)
    # the kernel's interface is head-dim minor; it swaps back to its layout
    return jax.vmap(kernel)(*(a.swapaxes(-1, -2) for a in (q, k, v)))


def selective_scan(xc, dt, a, b, c, d_skip, **kw):
    kw.setdefault("interpret", _default_interpret())
    return mamba_scan(xc, dt, a, b, c, d_skip, **kw)


def lru_scan(a, b, **kw):
    kw.setdefault("interpret", _default_interpret())
    return rglru_scan(a, b, **kw)


def grouped_matmul(x, w, **kw):
    kw.setdefault("interpret", _default_interpret())
    return moe_gmm(x, w, **kw)


def bag_lookup(tables, indices, **kw):
    kw.setdefault("interpret", _default_interpret())
    return embedding_bag(tables, indices, **kw)
