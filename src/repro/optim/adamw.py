"""Optimizers in pure JAX (no optax dependency).

State layout mirrors params so the sharding specs of parameters transfer
directly to the moments (ZeRO via parallel.sharding.opt_state_sharding).
When params are bf16, an fp32 master copy is kept in the state.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable
    update: Callable  # (grads, state, params, step) -> (new_params, new_state)


def _needs_master(p):
    return p.dtype in (jnp.bfloat16, jnp.float16)


def adamw(
    lr_fn: Callable[[jax.Array], jax.Array],
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    master_fp32: bool = True,
) -> Optimizer:
    def init(params):
        state = {
            "m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        }
        if master_fp32:
            state["master"] = jax.tree.map(
                lambda p: p.astype(jnp.float32) if _needs_master(p) else p, params
            )
        return state

    def update(grads, state, params, step):
        with jax.named_scope("adamw"):
            return _update(grads, state, params, step)

    def _update(grads, state, params, step):
        lr = lr_fn(step)
        t = (step + 1).astype(jnp.float32)
        c1 = 1.0 - b1**t
        c2 = 1.0 - b2**t
        source = state.get("master", params)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mh = m / c1
            vh = v / c2
            pf = p.astype(jnp.float32)
            step_vec = mh / (jnp.sqrt(vh) + eps) + weight_decay * pf
            new_p = pf - lr * step_vec
            return m, v, new_p

        flat_g, treedef = jax.tree.flatten(grads)
        flat_m = treedef.flatten_up_to(state["m"])
        flat_v = treedef.flatten_up_to(state["v"])
        flat_p = treedef.flatten_up_to(source)
        flat_orig = treedef.flatten_up_to(params)

        new_m, new_v, new_master, new_params = [], [], [], []
        for g, m, v, p, orig in zip(flat_g, flat_m, flat_v, flat_p, flat_orig):
            m2, v2, p2 = upd(g, m, v, p)
            new_m.append(m2)
            new_v.append(v2)
            new_master.append(p2 if _needs_master(orig) else p2.astype(orig.dtype))
            new_params.append(p2.astype(orig.dtype))

        new_state = {
            "m": jax.tree.unflatten(treedef, new_m),
            "v": jax.tree.unflatten(treedef, new_v),
        }
        if "master" in state:
            new_state["master"] = jax.tree.unflatten(treedef, new_master)
        return jax.tree.unflatten(treedef, new_params), new_state

    return Optimizer(init=init, update=update)


def sgd_momentum(
    lr_fn: Callable[[jax.Array], jax.Array], momentum: float = 0.9
) -> Optimizer:
    def init(params):
        return {"mom": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def update(grads, state, params, step):
        lr = lr_fn(step)

        def upd(g, m, p):
            m = momentum * m + g.astype(jnp.float32)
            return m, (p.astype(jnp.float32) - lr * m).astype(p.dtype)

        pairs = jax.tree.map(upd, grads, state["mom"], params)
        mom = jax.tree.map(lambda x: x[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
        new_p = jax.tree.map(lambda x: x[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"mom": mom}

    return Optimizer(init=init, update=update)


def apply_updates(params, updates):
    return jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)
