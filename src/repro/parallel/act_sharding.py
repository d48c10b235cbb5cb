"""Activation sharding constraints (GSPMD hints).

Without explicit constraints the partitioner may satisfy an FSDP-sharded
weight contraction by *replicating the batch* and all-reducing activations
(observed: f32[256,4096,896] activation all-reduces in the granite-8b HLO —
see EXPERIMENTS.md §Perf iteration 0).  Constraining activations to
batch-over-data at block boundaries forces the intended schedule: all-gather
the (small) layer weights, keep activations sharded.

The policy is process-global (models are pure functions of (params, batch));
step builders install it before lowering.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import Mesh, PartitionSpec as P


@dataclass(frozen=True)
class ActivationPolicy:
    dp: tuple | str | None  # axes for the batch dim
    tp: str | None  # axis for feature/head dims
    seq: str | None = None  # axis for the sequence dim (sequence parallelism)
    mesh: Mesh | None = None  # the mesh the axes name


_POLICY: ActivationPolicy | None = None


def set_policy(policy: ActivationPolicy | None) -> None:
    global _POLICY
    _POLICY = policy


def get_policy() -> ActivationPolicy | None:
    return _POLICY


def constrain(x, kind: str):
    """Apply a sharding constraint by activation kind.

    kinds: 'btd' (batch, seq, features), 'bd' (batch, features),
    'btf' (batch, seq, sharded features), 'ecd' (expert, capacity, features).
    No-op when no policy is installed (pure single-device use).
    """
    pol = _POLICY
    if pol is None:
        return x
    if kind == "btd":
        spec = P(pol.dp, pol.seq, None)
    elif kind == "bd":
        spec = P(pol.dp, None)
    elif kind == "btf":
        spec = P(pol.dp, pol.seq, pol.tp)
    elif kind == "ecd":
        spec = P(pol.tp, None, None)
    elif kind == "nd":  # flattened token dim (B*S or N*K, features)
        spec = P(pol.dp, None)
    else:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        # outside a mesh context (e.g. plain CPU tests) — no-op
        return x
