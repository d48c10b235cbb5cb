"""Model primitives shared by all assigned architectures.

Pure-function style: every layer is ``f(params_subtree, inputs, cfg) -> out``
so stacks can be driven by ``lax.scan`` over stacked parameters.  Norms and
softmax accumulate in fp32; matmul inputs are cfg.activation_dtype.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from .. import telemetry


def truncated_normal(key, shape, scale, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return truncated_normal(key, (d_in, d_out), scale, dtype)


# ---------------------------------------------------------------------------
# Norms / positional
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps: float = 1e-6):
    from ..parallel.options import get_options

    dt = x.dtype
    xf = x.astype(jnp.float32)
    scale = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if get_options().lowp_norm and dt != jnp.float32:
        # statistics in fp32, elementwise scaling in bf16: the residual
        # stream never materializes in fp32 (§Perf memory lever).
        return x * scale.astype(dt) * (1.0 + w.astype(jnp.float32)).astype(dt)
    return (xf * scale * (1.0 + w.astype(jnp.float32))).astype(dt)


def rope_frequencies(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: (..., S) int."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, causal / bidirectional / sliding-window, self / cross)
# ---------------------------------------------------------------------------


def init_attention(key, cfg, cross: bool = False):
    dt = jnp.dtype(cfg.param_dtype)
    hd = cfg.hd
    kq, kk, kv, ko, kg = jax.random.split(key, 5)
    p = {
        "wq": dense_init(kq, cfg.d_model, cfg.n_heads * hd, dt),
        "wk": dense_init(kk, cfg.d_model, cfg.n_kv_heads * hd, dt),
        "wv": dense_init(kv, cfg.d_model, cfg.n_kv_heads * hd, dt),
        "wo": dense_init(ko, cfg.n_heads * hd, cfg.d_model, dt),
        "norm": jnp.zeros((cfg.d_model,), dt),
    }
    if cross:
        # Llama-3.2-vision style gated cross-attention.
        p["gate"] = jnp.zeros((), dt)
        p["xnorm"] = jnp.zeros((cfg.d_model,), dt)
    return p


def _sdpa(q, k, v, mask):
    """q: (B, S, KV, G, D); k/v: (B, T, KV, D); mask: broadcastable (S, T)."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out


def _split_heads(x, n_heads, head_dim):
    return x.reshape(*x.shape[:-1], n_heads, head_dim)


def causal_mask(s: int, t: int, q_offset=0, window: int = 0):
    """(s, t) bool mask; query i attends key j iff j <= i+off and within
    window (if window > 0)."""
    qi = jnp.arange(s)[:, None] + q_offset
    kj = jnp.arange(t)[None, :]
    m = kj <= qi
    if window > 0:
        m &= kj > qi - window
    return m


def _chunked_sdpa(qg, k, v, *, causal: bool, window: int, chunk: int):
    """Flash-style online-softmax attention over KV chunks (XLA path).

    Never materializes the (S, T) score matrix — peak intermediate is
    (B, S, KV, G, chunk).  The Pallas kernel (kernels/flash_attention.py)
    is the TPU-native equivalent; this keeps the dry-run HLO honest.
    qg: (B, S, KV, G, D); k/v: (B, T, KV, D).
    """
    B, S, KV, G, D = qg.shape
    T = k.shape[1]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (T + pad) // chunk
    scale = 1.0 / math.sqrt(D)
    k_c = jnp.moveaxis(k.reshape(B, nc, chunk, KV, D), 1, 0)
    v_c = jnp.moveaxis(v.reshape(B, nc, chunk, KV, D), 1, 0)
    q_pos = jnp.arange(S)[:, None]

    def body(carry, xs):
        m, l, acc = carry
        kc, vc, ci = xs
        s = jnp.einsum("bskgd,btkd->bskgt", qg, kc).astype(jnp.float32) * scale
        k_pos = ci * chunk + jnp.arange(chunk)[None, :]
        msk = k_pos < T
        if causal:
            msk &= k_pos <= q_pos
        if window > 0:
            msk &= k_pos > q_pos - window
        s = jnp.where(msk[None, :, None, None, :], s, -1e30)
        m2 = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m2)
        p_ = jnp.exp(s - m2[..., None])
        l2 = alpha * l + p_.sum(axis=-1)
        acc2 = acc * alpha[..., None] + jnp.einsum(
            "bskgt,btkd->bskgd", p_.astype(vc.dtype), vc
        ).astype(jnp.float32)
        return (m2, l2, acc2), None

    init = (
        jnp.full((B, S, KV, G), -1e30, jnp.float32),
        jnp.zeros((B, S, KV, G), jnp.float32),
        jnp.zeros((B, S, KV, G, D), jnp.float32),
    )
    (m, l, acc), _ = lax.scan(
        jax.checkpoint(body), init, (k_c, v_c, jnp.arange(nc))
    )
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qg.dtype)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _fused_attention(x, p, cfg, causal: bool, window: int):
    """The fused attention kernel (``kernels.ops.fused_attention``) placed
    per shard, as a function of sequence-minor (q, k, v); None where it
    cannot run.  x: (B, S, d_model), the layer's input; p its weights.

    It runs on the TPU, for a sequence that is a multiple of the kernel's
    block, where each shard can attend on its own: inside ``shard_map``;
    under ``shard_map`` over the mesh that the arrays' types name (a mesh
    of explicit axes), with x's batch sharding and the weights whole; else
    over the activation policy's mesh (``act_sharding.get_policy``), the
    batch over its data axes and heads over ``tp``.  A sequence or
    feature dimension sharded, or a batch or head count the mesh does not
    divide, keeps the XLA path: a Pallas call left to GSPMD runs
    replicated and would gather the activations onto every device."""
    from ..kernels import ops
    from ..parallel.act_sharding import get_policy

    B, S, _ = x.shape
    if not _on_tpu() or S % ops.SEQ_BLOCK:
        return None
    kernel = partial(ops.fused_attention, causal=causal, window=window)
    context = jax.sharding.get_abstract_mesh()
    if context.manual_axes:
        # inside shard_map: the arrays are this device's shards already
        return kernel if set(context.manual_axes) == set(context.axis_names) else None
    typed = jax.typeof(x).sharding
    if AxisType.Explicit in typed.mesh.axis_types:
        b, s, d = (tuple(typed.spec) + (None,) * 3)[:3]
        whole = all(a is None for w in ("wq", "wk", "wv")
                    for a in jax.typeof(p[w]).sharding.spec)
        if s is not None or d is not None or not whole:
            return None
        mesh, spec = typed.mesh, P(b, None, None, None)
    else:
        pol = get_policy()
        if pol is not None and pol.seq is not None:
            return None
        if pol is None or pol.mesh is None:
            return kernel

        def size(axes):
            axes = () if axes is None else (axes,) if isinstance(axes, str) else axes
            return math.prod(pol.mesh.shape[a] for a in axes)

        tp = size(pol.tp)
        if B % size(pol.dp) or cfg.n_heads % tp or cfg.n_kv_heads % tp:
            return None
        # Auto axes, as the policy's constraints are hints: the output's
        # type carries no sharding, as the other activations' do not.
        mesh = Mesh(pol.mesh.devices, pol.mesh.axis_names,
                    axis_types=(AxisType.Auto,) * len(pol.mesh.axis_names))
        spec = P(pol.dp, pol.tp, None, None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def _fused_self_attention(p, x, cfg, kernel, positions, use_rope):
    """Self-attention through the fused kernel.  q, k and v are made with
    the sequence minor, (B, heads, head_dim, S), the layout the kernel
    reads and one that tiles without padding; RoPE and the 1/sqrt(d) scale
    are applied together in float32 and rounded once.  Returns
    (B, S, d_model)."""
    hd = cfg.hd
    B, S, d = x.shape

    def heads(w, n):
        return jnp.einsum("bsd,de->bes", x, w).reshape(B, n, hd, S)

    q, k, v = (heads(p["wq"], cfg.n_heads), heads(p["wk"], cfg.n_kv_heads),
               heads(p["wv"], cfg.n_kv_heads))
    scale = 1.0 / math.sqrt(hd)
    if use_rope:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = _rope_seq_minor(q, positions, cfg.rope_theta, scale)
        k = _rope_seq_minor(k, positions, cfg.rope_theta)
    else:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    out = kernel(q, k, v)  # (B, H, S, hd)
    return jnp.einsum("bhsk,hkd->bsd", out, p["wo"].reshape(cfg.n_heads, hd, d))


def _rope_seq_minor(x, positions, theta: float, scale: float = 1.0):
    """:func:`apply_rope` on x: (B, H, D, S), times ``scale``."""
    d = x.shape[-2]
    freqs = rope_frequencies(d, theta)  # (D/2,)
    angles = freqs[:, None] * positions[:, None, None, :].astype(jnp.float32)
    sin, cos = jnp.sin(angles), jnp.cos(angles)  # (B, 1, D/2, S)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-2)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-2)
    return (out * scale).astype(x.dtype)


def attention(p, x, cfg, *, mask=None, causal=True, window=0, positions=None,
              kv_x=None, use_rope=True):
    """Self- or cross-attention over full sequences (train / prefill).

    x: (B, S, d_model); kv_x: (B, T, d_model) for cross-attention.
    ``mask`` overrides (causal, window) for the naive path.  Self-attention
    with no explicit mask runs the fused kernel where
    :func:`_fused_attention` finds it can; every other case, and the
    fused kernel's refusals, take the XLA path that
    ``ModelOptions.attention_impl`` picks.  Each call traced counts
    ``attention.fused`` or ``attention.xla`` on the innermost open
    ``repro.telemetry`` span.  Returns (B, S, d_model).
    """
    if kv_x is None and mask is None and (causal or not window):
        fused = _fused_attention(x, p, cfg, causal, window)
        if fused is not None:
            telemetry.count("attention.fused")
            return _fused_self_attention(p, x, cfg, fused, positions, use_rope)
    telemetry.count("attention.xla")
    from ..parallel.options import get_options

    hd = cfg.hd
    src = x if kv_x is None else kv_x
    q = _split_heads(jnp.einsum("bsd,de->bse", x, p["wq"]), cfg.n_heads, hd)
    k = _split_heads(jnp.einsum("btd,de->bte", src, p["wk"]), cfg.n_kv_heads, hd)
    v = _split_heads(jnp.einsum("btd,de->bte", src, p["wv"]), cfg.n_kv_heads, hd)
    if use_rope and kv_x is None:
        if positions is None:
            positions = jnp.arange(x.shape[1])[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(*q.shape[:2], cfg.n_kv_heads, g, hd)

    opts = get_options()
    if opts.attention_impl == "chunked" and kv_x is None:
        out = _chunked_sdpa(
            qg, k, v, causal=causal, window=window, chunk=opts.attention_chunk
        )
    else:
        if mask is None and kv_x is None and (causal or window):
            mask = causal_mask(x.shape[1], src.shape[1], window=window)
        out = _sdpa(qg, k, v, mask)
    out = out.reshape(*out.shape[:2], cfg.n_heads * hd)
    return jnp.einsum("bse,ed->bsd", out, p["wo"])


def attention_decode(p, x, cache_k, cache_v, pos, cfg, *, window: int = 0):
    """One-token decode against a KV cache.

    x: (B, d_model); cache_k/v: (B, KV, T, D); pos: scalar current index.
    Returns (out (B, d_model), new_k, new_v).
    """
    hd = cfg.hd
    B = x.shape[0]
    q = _split_heads(jnp.einsum("bd,de->be", x, p["wq"]), cfg.n_heads, hd)
    k = _split_heads(jnp.einsum("bd,de->be", x, p["wk"]), cfg.n_kv_heads, hd)
    v = _split_heads(jnp.einsum("bd,de->be", x, p["wv"]), cfg.n_kv_heads, hd)
    posb = jnp.full((B, 1), pos)
    q = apply_rope(q[:, None], posb, cfg.rope_theta)[:, 0]
    k = apply_rope(k[:, None], posb, cfg.rope_theta)[:, 0]

    T = cache_k.shape[2]
    if window > 0 and window == T:
        # Rolling window cache: slot = pos % window.
        slot = pos % T
    else:
        slot = pos
    # All start indices must share one dtype; literal zeros would promote
    # to int64 when x64 is enabled (the planner pins it) while a traced
    # `pos` stays int32, so build them from slot's own dtype.
    slot = jnp.asarray(slot)
    zero = jnp.zeros((), dtype=slot.dtype)
    new_k = lax.dynamic_update_slice(
        cache_k, k[:, :, None, :].astype(cache_k.dtype), (zero, zero, slot, zero)
    )
    new_v = lax.dynamic_update_slice(
        cache_v, v[:, :, None, :].astype(cache_v.dtype), (zero, zero, slot, zero)
    )

    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, g, hd)
    scale = 1.0 / math.sqrt(hd)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, new_k).astype(jnp.float32) * scale
    t_idx = jnp.arange(T)
    if window > 0 and window == T:
        valid = (t_idx <= slot) | (pos >= T)  # whole ring valid once wrapped
    else:
        valid = t_idx <= pos
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs.astype(new_v.dtype), new_v)
    out = out.reshape(B, cfg.n_heads * hd)
    return jnp.einsum("be,ed->bd", out, p["wo"]), new_k, new_v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg, kind: str = "swiglu", d_ff: int | None = None):
    dt = jnp.dtype(cfg.param_dtype)
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "wg": dense_init(k1, cfg.d_model, d_ff, dt),
            "wu": dense_init(k2, cfg.d_model, d_ff, dt),
            "wd": dense_init(k3, d_ff, cfg.d_model, dt),
            "norm": jnp.zeros((cfg.d_model,), dt),
        }
    return {  # gelu
        "w1": dense_init(k1, cfg.d_model, d_ff, dt),
        "w2": dense_init(k2, d_ff, cfg.d_model, dt),
        "norm": jnp.zeros((cfg.d_model,), dt),
    }


def mlp(p, x):
    if "wg" in p:
        h = jax.nn.silu(jnp.einsum("...d,df->...f", x, p["wg"]))
        h = h * jnp.einsum("...d,df->...f", x, p["wu"])
        return jnp.einsum("...f,fd->...d", h, p["wd"])
    h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, p["w1"]))
    return jnp.einsum("...f,fd->...d", h, p["w2"])


# ---------------------------------------------------------------------------
# Mixture-of-Experts (capacity-based token dropping, sort-free dispatch)
# ---------------------------------------------------------------------------


def init_moe(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    kr, kg, ku, kd = jax.random.split(key, 4)
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    s = 1.0 / math.sqrt(D)
    return {
        "router": dense_init(kr, D, E, jnp.float32),
        "wg": truncated_normal(kg, (E, D, F), s, dt),
        "wu": truncated_normal(ku, (E, D, F), s, dt),
        "wd": truncated_normal(kd, (E, F, D), 1.0 / math.sqrt(F), dt),
        "norm": jnp.zeros((D,), dt),
    }


def moe(p, x, cfg):
    """Top-k routed MoE with per-expert capacity (GShard-style dropping).

    Dispatch uses argsort + scatter into an (E, C, D) buffer — no O(N*E*C)
    one-hot einsum — then three batched expert matmuls, then gather+combine.
    Returns (out, aux_loss).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    N = B * S
    xt = x.reshape(N, D)

    logits = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = lax.top_k(probs, K)  # (N, K)
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # Load-balancing aux loss (Switch): E * sum_e f_e * p_e.
    token_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=1), axis=0
    )
    prob_frac = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(token_frac * prob_frac) / K

    C = max(1, int(cfg.capacity_factor * N * K / E))

    flat_e = top_idx.reshape(-1)  # (N*K,)
    order = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(N * K) - starts[sorted_e]
    keep = pos_in_e < C
    slot = jnp.where(keep, pos_in_e, 0)

    from ..parallel.act_sharding import constrain as _constrain
    from ..parallel.options import get_options as _get_options

    tok_of = order // K  # source token per dispatch entry
    dispatched = jnp.where(keep[:, None], xt[tok_of], 0.0)
    if _get_options().moe_gather_constrain:
        dispatched = _constrain(dispatched, "nd")
    buf = jnp.zeros((E, C, D), xt.dtype)
    buf = buf.at[sorted_e, slot].add(dispatched, mode="drop")

    if _get_options().moe_constrain:
        buf = _constrain(buf, "ecd")

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wu"])
    y = jnp.einsum("ecf,efd->ecd", h, p["wd"])
    if _get_options().moe_constrain:
        y = _constrain(y, "ecd")

    gathered = y[sorted_e, slot]  # (N*K, D)
    w = top_vals.reshape(-1)[order]
    gathered = gathered * jnp.where(keep, w, 0.0)[:, None].astype(y.dtype)
    out = jnp.zeros((N, D), y.dtype).at[tok_of].add(gathered, mode="drop")
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# Linear recurrences (chunked associative scan): Mamba-1 + RG-LRU
# ---------------------------------------------------------------------------


def chunked_linear_scan(a, b, h0, chunk: int = 256):
    """Elementwise recurrence h_t = a_t * h_{t-1} + b_t along axis 1.

    a, b: (B, L, ...); h0: (B, ...).  Returns (h_all (B, L, ...), h_last).
    Chunking bounds the materialized prefix tree to (B, chunk, ...) per step
    so 32k/524k sequences don't blow activation memory.
    """
    Bsz, L = a.shape[0], a.shape[1]
    chunk = min(chunk, L)
    pad = (-L) % chunk
    if pad:
        a = jnp.concatenate(
            [a, jnp.ones((Bsz, pad, *a.shape[2:]), a.dtype)], axis=1
        )
        b = jnp.concatenate(
            [b, jnp.zeros((Bsz, pad, *b.shape[2:]), b.dtype)], axis=1
        )
    nc = a.shape[1] // chunk
    a_c = jnp.moveaxis(a.reshape(Bsz, nc, chunk, *a.shape[2:]), 1, 0)
    b_c = jnp.moveaxis(b.reshape(Bsz, nc, chunk, *b.shape[2:]), 1, 0)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    def body(h, ab):
        ac, bc = ab  # (B, chunk, ...)
        aa, bb = lax.associative_scan(combine, (ac, bc), axis=1)
        h_all = aa * h[:, None] + bb
        return h_all[:, -1], h_all

    from ..parallel.options import get_options

    if get_options().scan_impl == "assoc_ckpt":
        # recompute the within-chunk tree in the backward pass; only the
        # chunk-boundary carries are saved (§Perf memory lever).
        body = jax.checkpoint(body)
    h_last, h_all = lax.scan(body, h0, (a_c, b_c))
    h_all = jnp.moveaxis(h_all, 0, 1).reshape(Bsz, nc * chunk, *a.shape[2:])
    if pad:
        h_all = h_all[:, :L]
    return h_all, h_last


def causal_conv1d(x, w, prev=None):
    """Depthwise causal conv along time.  x: (B, L, D); w: (W, D).

    ``prev``: (B, W-1, D) carried context for decode/chunked prefill."""
    W = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev, x], axis=1)
    out = sum(
        xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(W)
    )
    new_prev = xp[:, -(W - 1) :] if W > 1 else prev
    return out, new_prev


def init_mamba(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    D, DI, ST, R = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    ks = jax.random.split(key, 6)
    a_init = jnp.log(jnp.tile(jnp.arange(1, ST + 1, dtype=jnp.float32), (DI, 1)))
    return {
        "w_in": dense_init(ks[0], D, 2 * DI, dt),
        "conv_w": truncated_normal(ks[1], (cfg.d_conv, DI), 1.0 / math.sqrt(cfg.d_conv), dt),
        "conv_b": jnp.zeros((DI,), dt),
        "w_xdbc": dense_init(ks[2], DI, R + 2 * ST, dt),
        "w_dt": dense_init(ks[3], R, DI, dt),
        "b_dt": jnp.full((DI,), -4.6, jnp.float32),  # softplus^-1(0.01)
        "a_log": a_init,
        "d_skip": jnp.ones((DI,), jnp.float32),
        "w_out": dense_init(ks[4], DI, D, dt),
        "norm": jnp.zeros((D,), dt),
    }


def mamba_ssm(p, xc, cfg, h0=None, chunk: int = 256):
    """Selective scan given the post-conv activations xc: (B, L, DI).

    Two implementations (parallel.options.scan_impl):
    * "assoc" (baseline): materializes (B, chunk, DI, ST) decay/drive and
      runs a chunked associative scan — parallel but HBM-heavy,
    * "seq": sequential lax.scan over time computing decay/drive on the fly
      — the HLO analogue of the fused Pallas kernel's traffic profile.
    Returns (y (B, L, DI), h_last (B, DI, ST) fp32)."""
    from ..parallel.options import get_options

    Bsz, L, DI = xc.shape
    ST, R = cfg.ssm_state, cfg.dt_rank_
    xdbc = jnp.einsum("bld,de->ble", xc, p["w_xdbc"])
    dt_r, b_ssm, c_ssm = jnp.split(xdbc, [R, R + ST], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("blr,rd->bld", dt_r, p["w_dt"]).astype(jnp.float32)
        + p["b_dt"]
    )  # (B, L, DI)
    a = -jnp.exp(p["a_log"])  # (DI, ST)
    if h0 is None:
        h0 = jnp.zeros((Bsz, DI, ST), jnp.float32)

    if get_options().scan_impl == "seq" and L > 1:
        xs = (
            jnp.moveaxis(xc.astype(jnp.float32), 1, 0),
            jnp.moveaxis(dt, 1, 0),
            jnp.moveaxis(b_ssm.astype(jnp.float32), 1, 0),
            jnp.moveaxis(c_ssm.astype(jnp.float32), 1, 0),
        )

        def step(h, ts):
            x_t, dt_t, b_t, c_t = ts
            h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * x_t)[..., None] * b_t[
                :, None, :
            ]
            y_t = jnp.einsum("bds,bs->bd", h, c_t) + p["d_skip"] * x_t
            return h, y_t

        h_last, ys = lax.scan(step, h0, xs)
        y = jnp.moveaxis(ys, 0, 1)
        return y.astype(xc.dtype), h_last

    decay = jnp.exp(dt[..., None] * a)  # (B, L, DI, ST)
    drive = (dt * xc.astype(jnp.float32))[..., None] * b_ssm.astype(jnp.float32)[
        :, :, None, :
    ]  # (B, L, DI, ST)
    h_all, h_last = chunked_linear_scan(decay, drive, h0, chunk=chunk)
    y = jnp.einsum("blds,bls->bld", h_all, c_ssm.astype(jnp.float32))
    y = y + p["d_skip"] * xc.astype(jnp.float32)
    return y.astype(xc.dtype), h_last


def mamba_block(p, x, cfg, state=None, chunk: int = 256):
    """Full Mamba-1 block.  x: (B, L, D).  state: None (train) or dict with
    'conv' (B, W-1, DI) and 'ssm' (B, DI, ST) for stateful prefill/decode.
    Returns (out, new_state)."""
    xz = jnp.einsum("bld,de->ble", x, p["w_in"])
    xi, z = jnp.split(xz, 2, axis=-1)
    prev = state["conv"] if state is not None else None
    xc, new_conv = causal_conv1d(xi, p["conv_w"], prev)
    xc = jax.nn.silu(xc + p["conv_b"])
    h0 = state["ssm"] if state is not None else None
    y, h_last = mamba_ssm(p, xc, cfg, h0=h0, chunk=chunk)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bld,de->ble", y, p["w_out"])
    new_state = {"conv": new_conv.astype(x.dtype), "ssm": h_last}
    return out, new_state


def init_rglru(key, cfg):
    dt = jnp.dtype(cfg.param_dtype)
    D, DI = cfg.d_model, cfg.d_inner
    ks = jax.random.split(key, 7)
    return {
        "w_x": dense_init(ks[0], D, DI, dt),
        "w_y": dense_init(ks[1], D, DI, dt),  # gelu branch
        "conv_w": truncated_normal(ks[2], (4, DI), 0.5, dt),
        "conv_b": jnp.zeros((DI,), dt),
        "w_input_gate": dense_init(ks[3], DI, DI, dt),
        "w_rec_gate": dense_init(ks[4], DI, DI, dt),
        "lambda_p": jnp.linspace(0.9, 5.0, DI, dtype=jnp.float32),  # softplus domain
        "w_out": dense_init(ks[5], DI, D, dt),
        "norm": jnp.zeros((D,), dt),
    }


RGLRU_C = 8.0


def rglru_block(p, x, cfg, state=None, chunk: int = 256):
    """Griffin recurrent block: conv1d -> RG-LRU, gated by a GeLU branch.

    x: (B, L, D); state: None or {'conv': (B, 3, DI), 'lru': (B, DI) fp32}.
    Returns (out, new_state)."""
    xb = jnp.einsum("bld,de->ble", x, p["w_x"])
    yb = jax.nn.gelu(jnp.einsum("bld,de->ble", x, p["w_y"]))
    prev = state["conv"] if state is not None else None
    xc, new_conv = causal_conv1d(xb, p["conv_w"], prev)
    xc = xc + p["conv_b"]

    i_gate = jax.nn.sigmoid(
        jnp.einsum("bld,de->ble", xc, p["w_input_gate"]).astype(jnp.float32)
    )
    r_gate = jax.nn.sigmoid(
        jnp.einsum("bld,de->ble", xc, p["w_rec_gate"]).astype(jnp.float32)
    )
    log_a = -RGLRU_C * r_gate * jax.nn.softplus(p["lambda_p"])
    a = jnp.exp(log_a)
    gated_x = i_gate * xc.astype(jnp.float32)
    drive = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * gated_x

    h0 = state["lru"] if state is not None else jnp.zeros(
        (x.shape[0], cfg.d_inner), jnp.float32
    )
    h_all, h_last = chunked_linear_scan(a, drive, h0, chunk=chunk)
    out = jnp.einsum("bld,de->ble", (h_all.astype(x.dtype) * yb), p["w_out"])
    new_state = {"conv": new_conv.astype(x.dtype), "lru": h_last}
    return out, new_state
