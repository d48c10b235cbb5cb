"""Plain float32 reference of the depth-cut minicpm-2b training step, and
the weights the benchmark makes for it.

The architecture is the one the configuration file states (a llama-style
decoder: pre-norm blocks, RMSNorm with a ``1 + w`` gain, rotary position
embedding on halves of each head, causal softmax attention, a SwiGLU
feed-forward, input embedding tied to the output head, next-token cross
entropy with the last position masked), trained by AdamW under a
warmup-stable-decay schedule.  It is written here from that description in
straightforward ``jax.numpy``: every sum and matmul in float32 under
``jax.default_matmul_precision("highest")``.  Departures from the published
MiniCPM (its muP embedding, residual and logit scalings) are departures of
the configuration, which the reference follows.

To fit next to nothing else on one chip it runs block by block: the
forward pass keeps each block's input, the backward pass re-derives each
block's gradient from that input, and AdamW updates a block as soon as its
gradient is known.  ``quantize`` rounds every matmul input to symmetric
per-tensor int8 first, which is the control of the precision below
bfloat16.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_LEAVES = {"attn": ("wq", "wk", "wv", "wo", "norm"),
                "mlp": ("wg", "wu", "wd", "norm")}


def dims(config: dict) -> dict:
    return {
        "layers": config["num_hidden_layers"],
        "d": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "hd": config["head_dim"],
        "ff": config["intermediate_size"],
        "vocab": config["vocab_size"],
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def key_for(seed: int):
    seed = abs(int(seed))
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def init_params(key, dm: dict, dtype=jnp.bfloat16):
    """The model's weights from ``key_for(seed)``, in the layout the
    program's decoder takes: truncated normals (2 sigma) of scale
    1/sqrt(fan in), 0.02 for the embedding, zero norm gains, blocks stacked
    on a leading layer axis.  Pure: jit it to make the weights on the
    device in one call."""
    d, L, F = dm["d"], dm["layers"], dm["ff"]
    q_out, kv_out = dm["heads"] * dm["hd"], dm["kv_heads"] * dm["hd"]
    ke, kb = jax.random.split(key)
    shapes = {
        "attn": {"wq": (d, q_out), "wk": (d, kv_out), "wv": (d, kv_out),
                 "wo": (q_out, d)},
        "mlp": {"wg": (d, F), "wu": (d, F), "wd": (F, d)},
    }

    def tn(key, shape, scale):
        return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
                * scale).astype(dtype)

    blocks = {}
    keys = iter(jax.random.split(kb, 7))
    for part, leaves in shapes.items():
        blocks[part] = {
            name: tn(next(keys), (L, *shape), 1.0 / math.sqrt(shape[0]))
            for name, shape in leaves.items()
        }
        blocks[part]["norm"] = jnp.zeros((L, d), dtype)
    return {
        "final_norm": jnp.zeros((d,), dtype),
        "embed": tn(ke, (dm["vocab"], d), 0.02),
        "blocks": blocks,
    }


# -- the step, in float32 ---------------------------------------------------


def _q8(x):
    """Symmetric per-tensor int8 rounding of a matmul input (the control).
    The backward pass sees the rounding as the identity, as int8 training
    does."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quantize):
    if quantize:
        a, b = _q8(a), _q8(b)
    return jnp.matmul(a, b)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def rope(x, theta):
    """x: (B, S, H, D), rotating the two halves of each head."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def block(p, x, dm, quantize=False):
    """One decoder block on (B, S, d) float32."""
    B, S, _ = x.shape
    H, K, D = dm["heads"], dm["kv_heads"], dm["hd"]
    h = rms_norm(x, p["attn"]["norm"], dm["eps"])
    q = rope(_mm(h, p["attn"]["wq"], quantize).reshape(B, S, H, D), dm["theta"])
    k = rope(_mm(h, p["attn"]["wk"], quantize).reshape(B, S, K, D), dm["theta"])
    v = _mm(h, p["attn"]["wv"], quantize).reshape(B, S, K, D)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(D)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    att = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(scores, axis=-1), v)
    x = x + _mm(att.reshape(B, S, H * D), p["attn"]["wo"], quantize)
    h = rms_norm(x, p["mlp"]["norm"], dm["eps"])
    f = jax.nn.silu(_mm(h, p["mlp"]["wg"], quantize)) * _mm(h, p["mlp"]["wu"], quantize)
    return x + _mm(f, p["mlp"]["wd"], quantize)


def head_nll(final_norm, embed, x, targets, mask, dm, quantize=False):
    """Summed next-token negative log-likelihood of (B, s, d) positions."""
    h = rms_norm(x, final_norm, dm["eps"])
    logits = _mm(h, embed.T, quantize)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum((logz - gold) * mask)


class Reference:
    """The reference trainer.  ``params`` is a dict with ``final_norm``,
    ``embed`` and ``layers`` (a list of per-block dicts), all float32."""

    def __init__(self, dm: dict, opt: dict, quantize: bool = False,
                 head_chunk: int = 512):
        self.dm = dm
        self.opt = opt
        self.quantize = quantize
        self.head_chunk = head_chunk
        q = quantize
        self._fwd = jax.jit(partial(block, dm=dm, quantize=q))
        self._bwd = jax.jit(
            lambda p, x, g: jax.vjp(partial(block, dm=dm, quantize=q), p, x)[1](g))
        self._head = jax.jit(jax.value_and_grad(
            partial(head_nll, dm=dm, quantize=q), argnums=(0, 1, 2)))
        self._adam = jax.jit(self._adam_leaf, donate_argnums=(1, 2, 3))

    @staticmethod
    def from_program_layout(tree) -> dict:
        """Unstack the program's (layer-stacked) tree into float32 blocks."""
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        L = tree["blocks"]["attn"]["wq"].shape[0]
        layers = [
            {part: {n: f32(leaves[n][i]) for n in leaves}
             for part, leaves in tree["blocks"].items()}
            for i in range(L)
        ]
        return {"final_norm": f32(tree["final_norm"]),
                "embed": f32(tree["embed"]), "layers": layers}

    def lr(self, step: int) -> float:
        o = self.opt
        total = o["total_steps"]
        warmup = max(1, int(total * o["warmup_frac"]))
        decay_start = int(total * (1 - o["decay_frac"]))
        warm = min(1.0, (step + 1) / warmup)
        prog = min(max((step - decay_start) / max(total - decay_start, 1), 0.0), 1.0)
        return o["peak_lr"] * warm * (1.0 - (1.0 - o["final_frac"]) * prog)

    def _adam_leaf(self, g, p, m, v, lr, t):
        o = self.opt
        m = o["b1"] * m + (1 - o["b1"]) * g
        v = o["b2"] * v + (1 - o["b2"]) * g * g
        mh = m / (1 - o["b1"] ** t)
        vh = v / (1 - o["b2"] ** t)
        p = p - lr * (mh / (jnp.sqrt(vh) + o["eps"]) + o["weight_decay"] * p)
        return p, m, v

    def loss_and_grads(self, params, tokens, on_block_grad):
        """Mean next-token loss of ``tokens`` (B, S); calls
        ``on_block_grad(i, grads)`` for each block, last first, and returns
        ``(loss, grads of final_norm, grads of embed)``."""
        dm = self.dm
        tokens = jnp.asarray(tokens)
        B, S = tokens.shape
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        mask = jnp.concatenate([jnp.ones((B, S - 1), jnp.float32),
                                jnp.zeros((B, 1), jnp.float32)], axis=1)
        count = float(B * (S - 1))
        x = params["embed"][tokens]
        xs = []
        for p in params["layers"]:
            xs.append(x)
            x = self._fwd(p, x)
        nll = 0.0
        g_norm = jnp.zeros_like(params["final_norm"])
        g_embed = jnp.zeros_like(params["embed"])
        g_x = []
        c = self.head_chunk
        for s in range(0, S, c):
            val, (gn, ge, gx) = self._head(
                params["final_norm"], params["embed"], x[:, s:s + c],
                targets[:, s:s + c], mask[:, s:s + c])
            nll += float(val)
            g_norm = g_norm + gn
            g_embed = g_embed + ge
            g_x.append(gx)
        g = jnp.concatenate(g_x, axis=1) / count
        g_norm, g_embed = g_norm / count, g_embed / count
        for i in reversed(range(len(params["layers"]))):
            g_p, g = self._bwd(params["layers"][i], xs[i], g)
            xs[i] = None
            on_block_grad(i, g_p)
        g_embed = g_embed.at[tokens].add(g)
        return nll / count, g_norm, g_embed

    def train(self, params, batches, steps: int):
        """AdamW for ``steps`` steps from ``params`` (float32, updated in
        place).  Returns the losses and the first step's gradient norms by
        leaf of the program's layout."""
        state = {"m": jax.tree.map(jnp.zeros_like, params),
                 "v": jax.tree.map(jnp.zeros_like, params)}
        losses, first = [], None
        for step in range(steps):
            lr, t = self.lr(step), float(step + 1)
            sq: dict = {}

            def upd(holder, key, g, path):
                p, m, v = self._adam(g, holder[0][key], holder[1][key],
                                     holder[2][key], lr, t)
                holder[0][key], holder[1][key], holder[2][key] = p, m, v
                sq[path] = sq.get(path, 0.0) + float(jnp.sum(g * g))

            def on_block(i, g_p):
                for part, leaves in g_p.items():
                    holder = (params["layers"][i][part], state["m"]["layers"][i][part],
                              state["v"]["layers"][i][part])
                    for name, g in leaves.items():
                        upd(holder, name, g, f"blocks.{part}.{name}")

            with jax.default_matmul_precision("highest"):
                loss, g_norm, g_embed = self.loss_and_grads(
                    params, batches[step], on_block)
                top = (params, state["m"], state["v"])
                upd(top, "final_norm", g_norm, "final_norm")
                del g_norm
                upd(top, "embed", g_embed, "embed")
                del g_embed
            losses.append(loss)
            if first is None:
                first = {k: math.sqrt(v) for k, v in sq.items()}
        return losses, first


def leaf_paths(tree) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [".".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]


def change_norms_ref(params, p0_program_layout) -> dict:
    """Per-leaf (program layout) norm of the reference's change."""
    out = {"final_norm": float(jnp.linalg.norm(params["final_norm"] - p0_program_layout["final_norm"].astype(jnp.float32))),
           "embed": float(jnp.linalg.norm(params["embed"] - p0_program_layout["embed"].astype(jnp.float32)))}
    for part, leaves in p0_program_layout["blocks"].items():
        for name, stacked in leaves.items():
            sq = 0.0
            for i, layer in enumerate(params["layers"]):
                d = layer[part][name] - stacked[i].astype(jnp.float32)
                sq += float(jnp.sum(d * d))
            out[f"blocks.{part}.{name}"] = math.sqrt(sq)
    return out


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """The largest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's reference norm and the median
    leaf's.  ``keep`` limits the leaves compared."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    worst, at = 0.0, ""
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med)
        if gap > worst or not at:
            worst, at = gap, k
    return worst, at


def moving_leaves(grad_norms: dict, floor: float = 1e-3) -> set:
    """Leaves whose reference gradient is above ``floor`` times the median
    leaf's; the others move under Adam by round-off alone."""
    med = float(np.median(list(grad_norms.values())))
    return {k for k, v in grad_norms.items() if v >= floor * med}
