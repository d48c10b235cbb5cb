"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True on the CPU (the test suite) and False on the
TPU, where the kernels compile to Mosaic; any other backend raises, since
the kernels have no lowering there.  The XLA fallbacks live in
models/layers.py; these wrappers are the TPU fast path.
"""

from __future__ import annotations

import jax

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
