"""Uniform model facade used by train/serve/launch.

init(key, cfg) / loss_fn(params, batch, cfg) / prefill / decode_step all
dispatch on cfg.family.  Losses are next-token CE for decoder LMs and
masked-frame CE for the audio encoder.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ArchConfig
from . import recurrent, transformer


def init(key, cfg: ArchConfig):
    if cfg.family == "ssm":
        return recurrent.init_mamba_params(key, cfg)
    if cfg.family == "hybrid":
        return recurrent.init_griffin_params(key, cfg)
    return transformer.init_params(key, cfg)


def param_specs(cfg: ArchConfig):
    """Parameter ShapeDtypeStructs without allocating (for the dry-run)."""
    return jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))


def forward(params, batch, cfg: ArchConfig, remat: str = "full"):
    if cfg.family == "ssm":
        return recurrent.mamba_forward(params, cfg, batch["tokens"], remat=remat)
    if cfg.family == "hybrid":
        return recurrent.griffin_forward(params, cfg, batch["tokens"], remat=remat)
    if cfg.family == "audio":
        return transformer.forward(params, cfg, frames=batch["frames"], remat=remat)
    return transformer.forward(
        params, cfg, tokens=batch.get("tokens"),
        image_embeds=batch.get("image_embeds"), remat=remat,
    )


def _xent(logits, targets, mask):
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def _hidden_xent_chunked(x, head, targets, mask, chunk: int):
    """CE computed over sequence chunks so (B, S, V) logits are never fully
    materialized (memory-roofline optimization; see EXPERIMENTS.md §Perf)."""
    B, S, D = x.shape
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nc = x.shape[1] // chunk
    xs = (
        jnp.moveaxis(x.reshape(B, nc, chunk, D), 1, 0),
        jnp.moveaxis(targets.reshape(B, nc, chunk), 1, 0),
        jnp.moveaxis(mask.reshape(B, nc, chunk), 1, 0),
    )

    def body(acc, xs_c):
        xc, tc, mc = xs_c
        logits = jnp.einsum("bsd,dv->bsv", xc, head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = ((logz - gold) * mc).sum()
        return (acc[0] + nll, acc[1] + mc.sum()), None

    body = jax.checkpoint(body)
    (nll, cnt), _ = lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    return nll / jnp.maximum(cnt, 1.0)


def loss_fn(params, batch, cfg: ArchConfig, remat: str = "full",
            loss_chunk: int = 0, aux_weight: float = 0.01):
    """Scalar training loss (+ metrics dict).  For transformer stacks the
    LM head and the cross-entropy lie in the named scope ``head_loss``."""
    if cfg.family == "audio":
        targets = batch["labels"]
        mask = jnp.ones(targets.shape, jnp.float32)
    else:
        tokens = batch["tokens"]
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        mask = jnp.concatenate(
            [jnp.ones(tokens[:, 1:].shape, jnp.float32),
             jnp.zeros(tokens[:, :1].shape, jnp.float32)], axis=1,
        )
    if cfg.family == "ssm" or cfg.family == "hybrid":
        # recurrent stacks keep their own head; always the full CE
        logits, aux = forward(params, batch, cfg, remat=remat)
        loss = _xent(logits, targets, mask)
    else:
        x, aux = transformer.hidden_forward(
            params, cfg, tokens=batch.get("tokens"), frames=batch.get("frames"),
            image_embeds=batch.get("image_embeds"), remat=remat,
        )
        with jax.named_scope("head_loss"):
            head = transformer.head_weights(params)
            if loss_chunk > 0 and cfg.family != "audio":
                loss = _hidden_xent_chunked(x, head, targets, mask, loss_chunk)
            else:
                loss = _xent(jnp.einsum("bsd,dv->bsv", x, head), targets, mask)
    if cfg.family == "audio":
        return loss, {"xent": loss}
    total = loss + aux_weight * aux
    return total, {"xent": loss, "aux": aux}


def prefill(params, batch, cfg: ArchConfig, pad_to: int = 0):
    if cfg.family == "ssm":
        return recurrent.mamba_prefill(params, cfg, batch["tokens"])
    if cfg.family == "hybrid":
        return recurrent.griffin_prefill(params, cfg, batch["tokens"])
    if cfg.family == "audio":
        logits, _ = transformer.forward(params, cfg, frames=batch["frames"])
        return logits, {}
    return transformer.prefill(
        params, cfg, batch["tokens"], image_embeds=batch.get("image_embeds"),
        pad_to=pad_to,
    )


def decode_step(params, batch, cfg: ArchConfig):
    token, pos, cache = batch["token"], batch["pos"], batch["cache"]
    if cfg.family == "ssm":
        return recurrent.mamba_decode_step(params, cfg, token, pos, cache)
    if cfg.family == "hybrid":
        return recurrent.griffin_decode_step(params, cfg, token, pos, cache)
    return transformer.decode_step(params, cfg, token, pos, cache)
