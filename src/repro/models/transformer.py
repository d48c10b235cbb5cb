"""Transformer stacks: dense (llama-arch), MoE (qwen3-arch), VLM
(cross-attention image blocks), and encoder-only audio (hubert).

Layer stacks are ``lax.scan`` over stacked parameters (keeps HLO size O(1)
in depth) with configurable rematerialization.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from ..parallel.act_sharding import constrain
from . import layers as L


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)  # "full"


def _stack_init(key, n, init_fn):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_self_block(key, cfg: ArchConfig, mlp_kind: str = "swiglu"):
    ka, km = jax.random.split(key)
    blk = {"attn": L.init_attention(ka, cfg)}
    if cfg.family == "moe":
        blk["moe"] = L.init_moe(km, cfg)
    else:
        blk["mlp"] = L.init_mlp(km, cfg, kind=mlp_kind)
    return blk


def init_cross_block(key, cfg: ArchConfig):
    ka, km = jax.random.split(key)
    return {
        "attn": L.init_attention(ka, cfg, cross=True),
        "mlp": L.init_mlp(km, cfg, kind="swiglu"),
    }


def init_params(key, cfg: ArchConfig):
    ke, kb, kh, kx = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    params: dict = {"final_norm": jnp.zeros((cfg.d_model,), dt)}

    if cfg.family != "audio":
        params["embed"] = L.truncated_normal(ke, (cfg.vocab, cfg.d_model), 0.02, dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(kh, cfg.d_model, cfg.vocab, dt)

    mlp_kind = "gelu" if cfg.family == "audio" else "swiglu"
    if cfg.family == "vlm":
        n_super = cfg.n_layers // cfg.cross_attn_every
        inner = cfg.cross_attn_every - 1
        params["blocks"] = {
            "self": _stack_init(
                kb, n_super,
                lambda k: _stack_init(k, inner, partial(init_self_block, cfg=cfg)),
            ),
            "cross": _stack_init(kx, n_super, partial(init_cross_block, cfg=cfg)),
        }
    else:
        params["blocks"] = _stack_init(
            kb, cfg.n_layers, partial(init_self_block, cfg=cfg, mlp_kind=mlp_kind)
        )
    return params


def _self_block_apply(blk, x, cfg, mask, positions):
    h = x + L.attention(
        blk["attn"], L.rms_norm(x, blk["attn"]["norm"]), cfg,
        mask=mask, causal=cfg.family != "audio", window=cfg.attn_window,
        positions=positions,
        use_rope=cfg.family != "audio",
    )
    if "moe" in blk:
        y, aux = L.moe(blk["moe"], L.rms_norm(h, blk["moe"]["norm"]), cfg)
        return h + y, aux
    y = L.mlp(blk["mlp"], L.rms_norm(h, blk["mlp"]["norm"]))
    return h + y, jnp.float32(0.0)


def _cross_block_apply(blk, x, img, cfg):
    att = L.attention(
        blk["attn"], L.rms_norm(x, blk["attn"]["xnorm"]), cfg,
        kv_x=img, use_rope=False,
    )
    h = x + jnp.tanh(blk["attn"]["gate"].astype(jnp.float32)).astype(x.dtype) * att
    y = L.mlp(blk["mlp"], L.rms_norm(h, blk["mlp"]["norm"]))
    return h + y


def head_weights(params):
    """The (d_model, vocab) output projection: ``lm_head``, or the
    transposed embedding where the two are tied."""
    head = params.get("lm_head")
    return params["embed"].T if head is None else head


def forward(
    params,
    cfg: ArchConfig,
    tokens=None,
    frames=None,
    image_embeds=None,
    remat: str = "full",
):
    """Full-sequence forward -> (logits (B, S, V), aux_loss)."""
    x, aux = hidden_forward(params, cfg, tokens=tokens, frames=frames,
                            image_embeds=image_embeds, remat=remat)
    return jnp.einsum("bsd,dv->bsv", x, head_weights(params)), aux


def hidden_forward(params, cfg, tokens=None, frames=None, image_embeds=None,
                   remat: str = "full"):
    """The stack up to the final norm, before the LM head -> (hidden
    (B, S, D), aux_loss).  Named scopes ``embed`` and ``blocks`` mark the
    embedding lookup and the layer stack in the compiled program."""
    if cfg.family == "audio":
        x = frames
        S = x.shape[1]
        mask = None
    else:
        with jax.named_scope("embed"):
            x = constrain(
                params["embed"][tokens].astype(jnp.dtype(cfg.activation_dtype)),
                "btd",
            )
        S = tokens.shape[1]
        mask = None  # attention() builds/streams the mask per impl
    positions = jnp.arange(S)[None, :]

    def block_fn(carry, blk):
        h, aux = carry
        h2, a = _self_block_apply(blk, constrain(h, "btd"), cfg, mask, positions)
        return (constrain(h2, "btd"), aux + a), None

    block_fn = _remat(block_fn, remat)
    with jax.named_scope("blocks"):
        if cfg.family == "vlm":
            img = image_embeds.astype(x.dtype)

            def super_fn(carry, blk):
                inner_carry, _ = lax.scan(block_fn, carry, blk["self"])
                h, aux = inner_carry
                h = _cross_block_apply(blk["cross"], h, img, cfg)
                return (h, aux), None

            (x, aux), _ = lax.scan(super_fn, (x, jnp.float32(0.0)),
                                   params["blocks"])
        else:
            (x, aux), _ = lax.scan(block_fn, (x, jnp.float32(0.0)),
                                   params["blocks"])
    return L.rms_norm(x, params["final_norm"]), aux


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode
# ---------------------------------------------------------------------------


def prefill(params, cfg: ArchConfig, tokens, image_embeds=None, pad_to: int = 0):
    """Full-sequence forward that also materializes the KV cache.

    ``pad_to``: pad the cache sequence dim to this length so decode can
    append (serving uses max_len; the dry-run measures prefill alone).
    Returns (last-token logits (B, V), cache dict matching cache_specs)."""
    act = jnp.dtype(cfg.activation_dtype)
    x = constrain(params["embed"][tokens].astype(act), "btd")
    B, S = tokens.shape
    positions = jnp.arange(S)[None, :]
    mask = None  # attention() builds/streams the mask per impl
    hd = cfg.hd

    def kv_of(blk, h):
        src = L.rms_norm(h, blk["attn"]["norm"])
        k = L._split_heads(jnp.einsum("btd,de->bte", src, blk["attn"]["wk"]),
                           cfg.n_kv_heads, hd)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        v = L._split_heads(jnp.einsum("btd,de->bte", src, blk["attn"]["wv"]),
                           cfg.n_kv_heads, hd)
        return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)  # (B, KV, S, D)

    def block_fn(carry, blk):
        h, aux = carry
        h = constrain(h, "btd")
        k, v = kv_of(blk, h)
        h2, a = _self_block_apply(blk, h, cfg, mask, positions)
        return (constrain(h2, "btd"), aux + a), (k.astype(act), v.astype(act))

    if cfg.family == "vlm":
        img = image_embeds.astype(act)

        def xkv_of(blk):
            k = L._split_heads(jnp.einsum("btd,de->bte", img, blk["attn"]["wk"]),
                               cfg.n_kv_heads, hd)
            v = L._split_heads(jnp.einsum("btd,de->bte", img, blk["attn"]["wv"]),
                               cfg.n_kv_heads, hd)
            return k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

        def super_fn(carry, blk):
            inner_carry, kv = lax.scan(block_fn, carry, blk["self"])
            h, aux = inner_carry
            xk, xv = xkv_of(blk["cross"])
            h = _cross_block_apply(blk["cross"], h, img, cfg)
            return (h, aux), (kv, (xk.astype(act), xv.astype(act)))

        (x, _), (kv, xkv) = lax.scan(super_fn, (x, jnp.float32(0.0)),
                                     params["blocks"])
        ks, vs = kv  # (n_super, inner, B, KV, S, D)
        cache = {
            "k": ks.reshape(-1, *ks.shape[2:]),
            "v": vs.reshape(-1, *vs.shape[2:]),
            "xk": xkv[0],
            "xv": xkv[1],
        }
    else:
        (x, _), (ks, vs) = lax.scan(block_fn, (x, jnp.float32(0.0)),
                                    params["blocks"])
        cache = {"k": ks, "v": vs}

    if pad_to > S:
        pad = [(0, 0)] * 5
        pad[3] = (0, pad_to - S)
        cache["k"] = jnp.pad(cache["k"], pad)
        cache["v"] = jnp.pad(cache["v"], pad)

    x = L.rms_norm(x[:, -1], params["final_norm"])
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bd,dv->bv", x, head)
    return logits, cache


def _cross_decode(blk, x, xk, xv, cfg):
    """One-token cross-attention against cached image K/V."""
    import math as _m

    hd = cfg.hd
    B = x.shape[0]
    xin = L.rms_norm(x, blk["attn"]["xnorm"])
    q = L._split_heads(jnp.einsum("bd,de->be", xin, blk["attn"]["wq"]),
                       cfg.n_heads, hd)
    g = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(B, cfg.n_kv_heads, g, hd)
    scores = jnp.einsum("bkgd,bktd->bkgt", qg, xk).astype(jnp.float32)
    scores = scores / _m.sqrt(hd)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,bktd->bkgd", probs.astype(xv.dtype), xv)
    att = jnp.einsum("be,ed->bd", out.reshape(B, -1), blk["attn"]["wo"])
    h = x + jnp.tanh(blk["attn"]["gate"].astype(jnp.float32)).astype(x.dtype) * att
    return h + L.mlp(blk["mlp"], L.rms_norm(h, blk["mlp"]["norm"]))


def decode_step(params, cfg: ArchConfig, token, pos, cache):
    """One decode step.  token: (B,) int32; pos: scalar; cache per
    cache_specs.  Returns (logits (B, V), new cache)."""
    x = params["embed"][token].astype(jnp.dtype(cfg.activation_dtype))

    def block_fn(h, xs):
        blk, ck, cv = xs
        h = constrain(h, "bd")
        att, nk, nv = L.attention_decode(
            blk["attn"], L.rms_norm(h, blk["attn"]["norm"]), ck, cv, pos, cfg,
            window=cfg.attn_window,
        )
        h = h + att
        if "moe" in blk:
            y, _ = L.moe(blk["moe"], L.rms_norm(h, blk["moe"]["norm"])[:, None],
                         cfg)
            h = h + y[:, 0]
        else:
            h = h + L.mlp(blk["mlp"], L.rms_norm(h, blk["mlp"]["norm"]))
        return h, (nk, nv)

    if cfg.family == "vlm":
        n_super = cfg.n_layers // cfg.cross_attn_every
        inner = cfg.cross_attn_every - 1
        ks = cache["k"].reshape(n_super, inner, *cache["k"].shape[1:])
        vs = cache["v"].reshape(n_super, inner, *cache["v"].shape[1:])

        def super_fn(h, xs):
            blk, ck, cv, xk, xv = xs
            h, kv = lax.scan(block_fn, h, (blk["self"], ck, cv))
            h = _cross_decode(blk["cross"], h, xk, xv, cfg)
            return h, kv

        x, kv = lax.scan(super_fn, x,
                         (params["blocks"], ks, vs, cache["xk"], cache["xv"]))
        new_cache = {
            "k": kv[0].reshape(-1, *kv[0].shape[2:]),
            "v": kv[1].reshape(-1, *kv[1].shape[2:]),
            "xk": cache["xk"],
            "xv": cache["xv"],
        }
    else:
        x, kv = lax.scan(block_fn, x, (params["blocks"], cache["k"], cache["v"]))
        new_cache = {"k": kv[0], "v": kv[1]}

    x = L.rms_norm(x, params["final_norm"])
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = jnp.einsum("bd,dv->bv", x, head)
    return logits, new_cache
