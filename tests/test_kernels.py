"""Pallas kernels vs pure-jnp oracles (interpret=True), sweeping shapes and
dtypes per the deliverable requirements."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.embedding_bag import embedding_bag
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.rglru_scan import rglru_scan

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype != np.float32 else dict(
        rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize(
    "B,H,KV,S,D,causal,window",
    [
        (2, 4, 4, 256, 64, True, 0),     # MHA causal
        (1, 8, 2, 256, 128, True, 0),    # GQA 4:1
        (2, 4, 1, 384, 64, True, 0),     # MQA
        (2, 4, 4, 256, 64, False, 0),    # bidirectional (encoder)
        (1, 4, 2, 512, 64, True, 128),   # sliding window (griffin)
        (1, 2, 2, 128, 32, True, 0),     # small dims
    ],
)
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_flash_attention_vs_ref(B, H, KV, S, D, causal, window, dtype):
    q = jnp.array(RNG.standard_normal((B, H, S, D)), dtype)
    k = jnp.array(RNG.standard_normal((B, KV, S, D)), dtype)
    v = jnp.array(RNG.standard_normal((B, KV, S, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    expect = ref.ref_flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 256), (37, 53)])
def test_flash_attention_block_shapes(block_q, block_k):
    q = jnp.array(RNG.standard_normal((1, 2, 222, 64)), jnp.float32)
    k = jnp.array(RNG.standard_normal((1, 2, 222, 64)), jnp.float32)
    v = jnp.array(RNG.standard_normal((1, 2, 222, 64)), jnp.float32)
    out = flash_attention(
        q, k, v, causal=True, block_q=block_q, block_k=block_k, interpret=True
    )
    expect = ref.ref_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=3e-5, atol=3e-5
    )


@pytest.mark.parametrize(
    "B,L,DI,ST,block_d,chunk",
    [(2, 256, 64, 8, 32, 64), (1, 128, 128, 16, 128, 128), (3, 64, 32, 4, 16, 32)],
)
def test_mamba_scan_vs_ref(B, L, DI, ST, block_d, chunk):
    xc = jnp.array(RNG.standard_normal((B, L, DI)), jnp.float32)
    dt = jnp.array(RNG.uniform(0.001, 0.1, (B, L, DI)), jnp.float32)
    a = -jnp.array(RNG.uniform(0.5, 2.0, (DI, ST)), jnp.float32)
    b = jnp.array(RNG.standard_normal((B, L, ST)), jnp.float32)
    c = jnp.array(RNG.standard_normal((B, L, ST)), jnp.float32)
    d = jnp.array(RNG.standard_normal((DI,)), jnp.float32)
    y, h = mamba_scan(xc, dt, a, b, c, d, block_d=block_d, chunk=chunk,
                      interpret=True)
    yr, hr = ref.ref_mamba_scan(xc, dt, a, b, c, d)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize("B,L,D,block_d,chunk", [(2, 256, 64, 32, 64), (1, 96, 48, 48, 32)])
def test_rglru_scan_vs_ref(B, L, D, block_d, chunk, dtype):
    a = jnp.array(RNG.uniform(0.1, 0.99, (B, L, D)), dtype)
    b = jnp.array(RNG.standard_normal((B, L, D)), dtype)
    h_all, h_fin = rglru_scan(a, b, block_d=block_d, chunk=chunk, interpret=True)
    hr_all, hr_fin = ref.ref_rglru_scan(a, b)
    np.testing.assert_allclose(
        np.asarray(h_all), np.asarray(hr_all), rtol=5e-3, atol=5e-3
    )
    np.testing.assert_allclose(
        np.asarray(h_fin), np.asarray(hr_fin), rtol=5e-3, atol=5e-3
    )


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
@pytest.mark.parametrize(
    "E,C,D,F,bc,bf,bd", [(4, 128, 256, 128, 64, 64, 128), (8, 64, 64, 256, 64, 128, 64)]
)
def test_moe_gmm_vs_ref(E, C, D, F, bc, bf, bd, dtype):
    x = jnp.array(RNG.standard_normal((E, C, D)), dtype)
    w = jnp.array(RNG.standard_normal((E, D, F)) / np.sqrt(D), dtype)
    o = moe_gmm(x, w, block_c=bc, block_f=bf, block_d=bd, interpret=True)
    orf = ref.ref_moe_gmm(x, w)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(orf, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("T,R,E,B,NNZ", [(3, 50, 16, 2, 4), (1, 10, 8, 4, 1), (5, 100, 32, 3, 7)])
def test_embedding_bag_vs_ref(T, R, E, B, NNZ):
    tables = jnp.array(RNG.standard_normal((T, R, E)), jnp.float32)
    idx = jnp.array(RNG.integers(0, R, (B, T, NNZ)), jnp.int32)
    out = embedding_bag(tables, idx, interpret=True)
    expect = ref.ref_embedding_bag(tables, idx)
    # Kernel sums the bag sequentially, the reference via XLA's tree reduce;
    # both in fp32, so they differ only by summation order: bounded by
    # ~NNZ ulps of the partial-sum magnitude, which an atol floor covers for
    # bags whose terms nearly cancel (|sum| << |terms|).
    atol = NNZ * np.finfo(np.float32).eps * float(np.abs(np.asarray(tables)).max())
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect), rtol=1e-6, atol=atol
    )


def test_xla_fallback_matches_kernel_mamba():
    """models.layers chunked-scan fallback == Pallas kernel semantics."""
    from repro.models.layers import chunked_linear_scan

    B, L, DI, ST = 1, 64, 16, 4
    xc = jnp.array(RNG.standard_normal((B, L, DI)), jnp.float32)
    dt = jnp.array(RNG.uniform(0.01, 0.1, (B, L, DI)), jnp.float32)
    a = -jnp.array(RNG.uniform(0.5, 2.0, (DI, ST)), jnp.float32)
    bm = jnp.array(RNG.standard_normal((B, L, ST)), jnp.float32)
    cm = jnp.array(RNG.standard_normal((B, L, ST)), jnp.float32)
    d = jnp.zeros((DI,), jnp.float32)
    decay = jnp.exp(dt[..., None] * a)
    drive = (dt * xc)[..., None] * bm[:, :, None, :]
    h_all, _ = chunked_linear_scan(decay, drive, jnp.zeros((B, DI, ST)), chunk=16)
    y_fallback = jnp.einsum("blds,bls->bld", h_all, cm)
    y_kernel, _ = mamba_scan(xc, dt, a, bm, cm, d, block_d=16, chunk=16,
                             interpret=True)
    np.testing.assert_allclose(
        np.asarray(y_fallback), np.asarray(y_kernel), rtol=1e-4, atol=1e-4
    )


@pytest.mark.parametrize(
    "backend,interpret", [("cpu", True), ("tpu", False), ("gpu", None)]
)
def test_ops_interpret_only_on_cpu(monkeypatch, backend, interpret):
    """The wrappers interpret on the CPU, compile on the TPU, and refuse
    any other backend rather than fall back to the interpreter."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="no lowering"):
            ops._default_interpret()
    else:
        assert ops._default_interpret() is interpret
