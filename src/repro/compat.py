"""Process-wide JAX numerics for the planner backend.

:func:`ensure_x64` pins 64-bit JAX arithmetic for the planner backend
(:mod:`repro.core.planeval_jax`): the NumPy plan evaluator is float64, and
CPU CI must price candidates at the same precision on every run or the
JAX-vs-NumPy equivalence tolerances drift with the platform default.
"""

from __future__ import annotations

import os

# Truthiness table for JAX_ENABLE_X64-style env switches.
_FALSY = {"0", "false", "False", "FALSE", ""}


def ensure_x64(enable: bool | None = None) -> bool:
    """Enable (or explicitly pin) 64-bit JAX arithmetic, idempotently.

    ``enable=None`` honours an existing ``JAX_ENABLE_X64`` environment
    setting and defaults to *on* when unset — the deterministic-CI posture:
    the planner's JAX backend always prices candidates in float64, matching
    the NumPy reference, unless the environment explicitly opts out.
    Returns the effective x64 state.  Safe to call repeatedly, before or
    after other jax use (``jax.config.update`` is retroactive for newly
    minted arrays; the planner builds all of its arrays after this call).
    """
    import jax

    if enable is None:
        env = os.environ.get("JAX_ENABLE_X64")
        enable = True if env is None else env not in _FALSY
    jax.config.update("jax_enable_x64", bool(enable))
    return bool(jax.config.jax_enable_x64)
