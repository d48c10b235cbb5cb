"""JAX-native batched planner: jit/vmap port of the compiled plan evaluator
(ROADMAP open item 1 — "compile once, evaluate many", on accelerator).

:mod:`repro.core.planeval` compiles a fixed
:class:`~repro.core.topology_finder.Topology` into flat NumPy structure
arrays (link-id table, per-group ring-edge incidence, CSR route cache) and
prices one candidate demand per Python call.  Those arrays are already
array-shaped, so this module lifts the whole scatter + bottleneck-division
pipeline onto JAX:

* :func:`pack_demand` flattens one demand's pricing work into two flat
  arrays — per-occurrence link ids and per-occurrence byte shares (AllReduce
  ring-edge occurrences first, then MP route hops, exactly the occurrences
  the NumPy ``np.add.at`` scatters walk);
* :class:`JaxPlanEvaluator` pads K such packs to one static shape and
  prices all K demands in **one device dispatch**: a vmapped
  ``jax.ops.segment_sum`` scatter over the link universe followed by one
  vectorized ``max(loads / caps)`` bottleneck division;
* :class:`ChainKernel` runs K independent MCMC chains entirely on device:
  the per-tenant strategy space is pre-priced into a ``(tenants, pool,
  links)`` load-vector tensor, a chain state is one pool index per tenant,
  and ``lax.scan`` carries (state, objective, best) through all iterations
  with the annealing rule applied per step — one compiled dispatch for the
  whole batch of chains (vmapped over the chain axis, per-chain
  temperatures supported).

**Numerics.**  The NumPy path stays the bit-exact reference.
:func:`repro.compat.ensure_x64` pins float64 so the JAX pipeline prices the
same arithmetic — but ``segment_sum`` and ``jnp.sum`` may reassociate float
additions, so JAX results match the reference to ~1e-9 relative
(:data:`JAX_EQUIV_RTOL`), not to the bit.  Chain *semantics* are exactly
reproducible: every random draw (proposed tenant, proposed pool index,
acceptance uniform) is pre-drawn on host with ``random.Random(seed +
chain)`` (:func:`draw_proposal_streams`), and
:func:`run_chains_reference` re-runs the identical chain sequentially in
NumPy — ``tests/test_planeval_jax.py`` pins batched-vs-sequential agreement
at fixed seeds.  Because the JAX chain explores a *pre-priced pool* rather
than proposing unbounded host subsets per step, it is a documented
different chain from ``backend="numpy"`` (same annealing rule, different
move space) — the NumPy backend is byte-stable against it.

House style: the jit/parametrized idiom follows the jaxnet excerpts in
SNIPPETS.md (compile once at construction, apply many); the Pallas kernels
under :mod:`repro.kernels` own the lower-level accelerator hot loops.
"""

from __future__ import annotations

import math
import os
import random

import numpy as np

from ..compat import ensure_x64
from .netsim import HardwareSpec
from .planeval import PlanEvaluator, plan_evaluator

__all__ = [
    "JAX_EQUIV_RTOL",
    "DEFAULT_TEMPER_LADDER",
    "pack_demand",
    "JaxPlanEvaluator",
    "jax_plan_evaluator",
    "ChainKernel",
    "check_temper_ladder",
    "default_temper_ladder",
    "draw_proposal_streams",
    "draw_grid_streams",
    "draw_swap_streams",
    "run_chains_reference",
    "run_grid_reference",
    "strategy_pool",
    "pack_jobset_grid",
    "jax_mcmc_search",
    "jax_mcmc_search_jobset",
]

# Decorrelates the pool-construction RNG from the per-chain proposal
# streams (both are seeded from the caller's one seed).
_POOL_SEED_OFFSET = 0x9E3779B9

# Decorrelates the tempering swap uniforms from the proposal streams: a
# singleton ladder draws no swap uniforms, so the proposal streams (and
# with them every pre-ladder golden) are untouched by the ladder's
# introduction.
_SWAP_SEED_OFFSET = 0x85EBCA6B

# Default parallel-tempering ladder (ascending; the coldest rung matches
# the historical single-chain temperature=0.05 regime, the hottest rung
# explores).  Override with REPRO_TEMPER_LADDER="0.05,0.1,0.2,0.4".
DEFAULT_TEMPER_LADDER = (0.05, 0.1, 0.2, 0.4)


def check_temper_ladder(temperatures) -> tuple[float, ...]:
    """Validate a tempering ladder: non-empty, positive finite, ascending.

    Returns the ladder as a float tuple.  Neighbor swap moves pair rung
    ``m`` with ``m + 1``, so the ladder must be sorted coldest-first for
    the swap acceptance rule to mean what parallel tempering means.
    """
    ladder = tuple(float(t) for t in temperatures)
    if not ladder:
        raise ValueError("temperature ladder must be non-empty")
    for t in ladder:
        if not math.isfinite(t) or t <= 0.0:
            raise ValueError(
                "ladder temperatures must be positive and finite"
            )
    if any(b < a for a, b in zip(ladder, ladder[1:])):
        raise ValueError("temperature ladder must be sorted ascending")
    return ladder


def default_temper_ladder() -> tuple[float, ...]:
    """The tempering ladder fused admission uses when the caller passes
    ``temperatures=True``-style defaults: :data:`DEFAULT_TEMPER_LADDER`,
    overridable via the ``REPRO_TEMPER_LADDER`` env knob (comma-separated
    ascending floats, e.g. ``"0.05,0.1,0.2,0.4"``)."""
    env = os.environ.get("REPRO_TEMPER_LADDER", "").strip()
    if not env:
        return DEFAULT_TEMPER_LADDER
    return check_temper_ladder(float(x) for x in env.split(","))

# Documented JAX-vs-NumPy agreement: float64 throughout (ensure_x64), but
# segment_sum/jnp.sum reassociate additions the reference performs
# sequentially, so compiled values agree to reassociation level only.
JAX_EQUIV_RTOL = 1e-9

_jax = None


def _require_jax():
    """Import jax lazily (and exactly once), pinning x64 before first use."""
    global _jax
    if _jax is None:
        ensure_x64()
        import jax  # noqa: PLC0415

        _jax = jax
    return _jax


# ---------------------------------------------------------------------------
# Demand packing: one demand -> flat (link ids, byte shares) scatter arrays
# ---------------------------------------------------------------------------


def pack_demand(ev: PlanEvaluator, demand) -> tuple[np.ndarray, np.ndarray]:
    """Flatten ``demand`` into per-occurrence ``(link_ids, shares)``.

    The occurrence stream is exactly what the NumPy evaluator scatters:
    AllReduce groups in demand order (each group's ring edges in reference
    walk order, share ``2(k-1)/k * nbytes / n_rings``), then MP entries in
    ``np.nonzero`` order (each pair's route hops, share
    ``bytes / n_routes``).  ``segment_sum`` over these ids reproduces the
    reference load vector up to float reassociation.

    Compiles lazily through the shared :class:`PlanEvaluator` caches — pack
    every demand of a batch *before* reading ``ev.n_links``/``ev.caps`` so
    the link universe stops growing first.
    """
    pids, vals = ev._ensure_compiled(demand)
    ids_parts: list[np.ndarray] = []
    share_parts: list[np.ndarray] = []
    for g in demand.allreduce:
        entry = ev._group(g.members)
        if entry is None:
            continue
        ids, n_rings, k = entry
        per_link_total = 2.0 * (k - 1) / k * g.nbytes
        if per_link_total == 0.0:
            continue
        ids_parts.append(ids)
        share_parts.append(
            np.full(ids.size, per_link_total / n_rings, dtype=np.float64)
        )
    if pids.size:
        starts = ev._pair_start[pids]
        lens = ev._pair_len[pids]
        total = int(lens.sum())
        if total:
            seg_off = np.cumsum(lens) - lens
            idx = (
                np.arange(total, dtype=np.int64)
                - np.repeat(seg_off, lens)
                + np.repeat(starts, lens)
            )
            ids_parts.append(ev._mp_ids[idx])
            share_parts.append(
                np.repeat(vals / ev._pair_nroutes[pids], lens)
            )
    if not ids_parts:
        return (
            np.zeros(0, dtype=np.int64),
            np.zeros(0, dtype=np.float64),
        )
    return np.concatenate(ids_parts), np.concatenate(share_parts)


class JaxPlanEvaluator:
    """Batched demand pricing on device: K candidates, one dispatch.

    Wraps the (memoized) NumPy :class:`PlanEvaluator` of the same topology:
    structure compilation (link ids, ring incidence, routes) stays on host
    and is shared with every NumPy caller; only the scatter + bottleneck
    arithmetic moves to JAX.  Padding: each demand's occurrence stream is
    padded to the batch maximum with a sentinel id pointing one past the
    link universe (a dummy segment whose zero shares cannot leak into any
    real link).
    """

    def __init__(self, topo, hw: HardwareSpec):
        jax = _require_jax()
        self.ev = plan_evaluator(topo, hw)
        self.topo = topo
        self.hw = hw

        def _batched(idx, val, caps):
            n_links = caps.shape[0]

            def one(i, v):
                loads = jax.ops.segment_sum(
                    v, i, num_segments=n_links + 1
                )
                return jax.numpy.max(loads[:n_links] / caps)

            return jax.vmap(one)(idx, val)

        # jit recompiles per (K, pad, n_links) shape triple; shapes repeat
        # across MCMC steps, so steady-state runs hit the compile cache.
        self._batched = jax.jit(_batched)

    def pack(self, demands) -> tuple[np.ndarray, np.ndarray]:
        """Padded ``(K, pad)`` id/share arrays for a batch of demands (all
        compiled into the shared link universe first)."""
        packs = [pack_demand(self.ev, d) for d in demands]
        n_links = self.ev.n_links
        pad = max((ids.size for ids, _ in packs), default=0)
        idx = np.full((len(packs), max(pad, 1)), n_links, dtype=np.int64)
        val = np.zeros((len(packs), max(pad, 1)), dtype=np.float64)
        for row, (ids, shares) in enumerate(packs):
            idx[row, : ids.size] = ids
            val[row, : ids.size] = shares
        return idx, val

    def comm_times(self, demands) -> np.ndarray:
        """Bottleneck comm times of K demands in one device dispatch —
        agrees with :meth:`PlanEvaluator.comm_time` per demand to
        :data:`JAX_EQUIV_RTOL`."""
        demands = list(demands)
        if not demands:
            return np.zeros(0)
        idx, val = self.pack(demands)
        if self.ev.n_links:
            times = np.asarray(
                self._batched(idx, val, self.ev.caps), dtype=np.float64
            )
        else:
            times = np.zeros(len(demands))
        if self.hw.link_latency:
            from .demand import demand_steps

            times = times + self.hw.link_latency * np.asarray(
                [demand_steps(d) for d in demands]
            )
        return times

    def comm_time(self, demand) -> float:
        """Single-demand comm time through the batched kernel."""
        return float(self.comm_times([demand])[0])

    def comm(self, demand) -> dict[str, float]:
        """Drop-in for :meth:`PlanEvaluator.comm` with the comm time priced
        on device (the bandwidth tax reuses the host route cache — it is a
        per-pair average, not a hot-loop quantity)."""
        out = self.ev.comm(demand)
        return {
            "comm_time": self.comm_time(demand),
            "bandwidth_tax": out["bandwidth_tax"],
        }


def jax_plan_evaluator(topo, hw: HardwareSpec) -> JaxPlanEvaluator:
    """Memoized :class:`JaxPlanEvaluator` per (topology, hw) — the JAX
    analogue of :func:`~repro.core.planeval.plan_evaluator`, sharing its
    host-side structure caches."""
    cache = getattr(topo, "_jax_planevals", None)
    if cache is None:
        cache = {}
        topo._jax_planevals = cache
    ev = cache.get(hw)
    if ev is None:
        ev = JaxPlanEvaluator(topo, hw)
        cache[hw] = ev
    return ev


# ---------------------------------------------------------------------------
# Strategy pool: the pre-priced move space of the on-device chains
# ---------------------------------------------------------------------------


def strategy_pool(
    job, n: int, size: int, seed: int, init=None, schedules=None
) -> list:
    """A deterministic pool of ``size`` candidate strategies for one job.

    Index 0 is the chain's start state (``init`` or the cold default); the
    rest come from a fixed-seed random walk of the NumPy proposal kernel
    (:func:`~repro.core.strategy_search._propose`), deduplicated.  When the
    reachable space is smaller than ``size`` the pool is padded by cycling
    (duplicate entries are harmless: a move onto a duplicate prices
    identically to its twin).  ``schedules`` (a tuple of collective
    schedule names) widens the walk with schedule flips exactly as in the
    NumPy proposal kernel; ``None`` / single-entry keeps the walk (and its
    RNG stream) byte-identical to the pre-schedule pool.
    """
    from .strategy_search import _propose, default_strategy

    if size < 1:
        raise ValueError("strategy pool needs size >= 1")
    rng = random.Random(seed)
    current = init if init is not None else default_strategy(job)
    pool = [current]
    seen = {current}
    tries = 0
    while len(pool) < size and tries < 64 * size:
        cand = _propose(current, job, n, rng, schedules=schedules)
        tries += 1
        if cand not in seen:
            seen.add(cand)
            pool.append(cand)
        current = cand  # random-walk the space for coverage
    distinct = len(pool)
    while len(pool) < size:
        pool.append(pool[len(pool) % distinct])
    return pool


# ---------------------------------------------------------------------------
# Batched MCMC chains: K chains, one lax.scan, one dispatch
# ---------------------------------------------------------------------------


def draw_proposal_streams(
    seed: int, chains: int, iters: int, n_tenants: int, pool_size: int
):
    """Host-side randomness of K chains, pre-drawn and replayable.

    Chain ``c`` draws from ``random.Random(seed + c)`` in strict
    (tenant, pool index, acceptance uniform) per-iteration order — the
    exact stream :func:`run_chains_reference` replays sequentially, so the
    batched device run and the NumPy reference are the *same* chains.

    Returns ``(t_idx, s_idx, u)`` each of shape ``(chains, iters)``.
    """
    t_idx = np.zeros((chains, iters), dtype=np.int64)
    s_idx = np.zeros((chains, iters), dtype=np.int64)
    u = np.zeros((chains, iters), dtype=np.float64)
    for c in range(chains):
        rng = random.Random(seed + c)
        for i in range(iters):
            t_idx[c, i] = rng.randrange(n_tenants)
            s_idx[c, i] = rng.randrange(pool_size)
            u[c, i] = rng.random()
    return t_idx, s_idx, u


def draw_grid_streams(
    seed: int,
    candidates: int,
    chains: int,
    ladder: int,
    iters: int,
    n_tenants: int,
    pool_size: int,
):
    """:func:`draw_proposal_streams` lifted to the (candidate, temperature)
    grid: cell ``(ci, c, m)`` draws its own stream from
    ``random.Random(seed + c + _POOL_SEED_OFFSET * (ci * ladder + m))`` in
    the same strict (tenant, pool index, acceptance uniform) order.  The
    golden-ladder offset decorrelates cells while the degenerate cell
    ``(0, c, 0)`` reduces to exactly :func:`draw_proposal_streams`' chain
    ``c`` — the byte-identity anchor of the singleton-ladder contract.

    Returns ``(t_idx, s_idx, u)`` each of shape
    ``(candidates, chains, ladder, iters)``.
    """
    t_idx = np.zeros((candidates, chains, ladder, iters), dtype=np.int64)
    s_idx = np.zeros((candidates, chains, ladder, iters), dtype=np.int64)
    u = np.zeros((candidates, chains, ladder, iters), dtype=np.float64)
    for ci in range(candidates):
        for c in range(chains):
            for m in range(ladder):
                rng = random.Random(
                    seed + c + _POOL_SEED_OFFSET * (ci * ladder + m)
                )
                for i in range(iters):
                    t_idx[ci, c, m, i] = rng.randrange(n_tenants)
                    s_idx[ci, c, m, i] = rng.randrange(pool_size)
                    u[ci, c, m, i] = rng.random()
    return t_idx, s_idx, u


def draw_swap_streams(
    seed: int, candidates: int, chains: int, ladder: int, iters: int
) -> np.ndarray:
    """Pre-drawn swap-acceptance uniforms of the tempering ladder.

    One uniform per (iteration, neighbor pair) from a
    :data:`_SWAP_SEED_OFFSET`-shifted stream per (candidate, chain) — a
    singleton ladder has zero pairs and draws nothing, leaving the
    proposal streams byte-identical to the pre-ladder kernel.

    Returns shape ``(candidates, chains, iters, ladder // 2)``.
    """
    pairs = ladder // 2
    su = np.zeros((candidates, chains, iters, pairs), dtype=np.float64)
    for ci in range(candidates):
        for c in range(chains):
            rng = random.Random(
                seed + c + _SWAP_SEED_OFFSET + _POOL_SEED_OFFSET * ci
            )
            for i in range(iters):
                for p in range(pairs):
                    su[ci, c, i, p] = rng.random()
    return su


# Compiled grid programs, shared across ChainKernel instances: keyed by
# the scalar closure parameters; jax.jit then specializes per argument
# shape.  This is what lets the fused alternating loop rebuild its kernel
# every round (new load tensors, same shapes) without recompiling — the
# flat kernel keeps its per-instance jit (the PR 6 baseline semantics).
_GRID_PROGRAMS: dict = {}


def _grid_program(objective, overlap, alpha, total_w, has_steps):
    key = (objective, overlap, alpha, total_w, has_steps)
    fn = _GRID_PROGRAMS.get(key)
    if fn is not None:
        return fn
    jax = _require_jax()
    jnp = jax.numpy

    def _objective_rows(Vc, capsc, steps_d, w_d, comps_d, A):
        # A: (M, T) ladder of states -> (M,) objectives.  Identical
        # arithmetic to the flat kernel's _objective, vectorized over the
        # rung axis.
        T = A.shape[1]
        t_ar = jnp.arange(T)
        rows = Vc[t_ar[None, :], A]  # (M, T, L)
        if objective == "union":
            comm = jnp.max(rows.sum(axis=1) / capsc[None, :], axis=1)
            if has_steps:
                comm = comm + alpha * jnp.max(
                    steps_d[t_ar[None, :], A], axis=1
                )
            comm_t = jnp.broadcast_to(comm[:, None], A.shape)
        else:
            active = rows > 0.0
            active_w = jnp.sum(
                jnp.where(active, w_d[None, :, None], 0.0), axis=1
            )  # (M, L)
            per = jnp.where(
                active,
                rows * active_w[:, None, :]
                / (w_d[None, :, None] * capsc[None, None, :]),
                0.0,
            )
            comm_t = jnp.max(per, axis=2)  # (M, T)
            if has_steps:
                comm_t = comm_t + alpha * steps_d[t_ar[None, :], A]
        hidden = jnp.minimum(comm_t * overlap, comps_d[None, :])
        iters_t = comps_d[None, :] + comm_t - hidden
        return jnp.sum(w_d[None, :] * iters_t, axis=1) / total_w

    def _one_ladder(Vc, capsc, comps_d, w_d, steps_d, init_a, temps,
                    t_idx, s_idx, u, su, parity):
        M = t_idx.shape[0]
        P = su.shape[1]
        m_ar = jnp.arange(M)
        p_ar = jnp.arange(P)

        def step(carry, inp):
            A, cur, best_a, best = carry
            ti, si, ui, sui, par = inp
            # Per-rung annealing move (each rung mutates its own row).
            cand_A = A.at[m_ar, ti].set(si)
            cand = _objective_rows(Vc, capsc, steps_d, w_d, comps_d,
                                   cand_A)
            temp = temps * jnp.maximum(cur, 1e-12)
            accept = (cand <= cur) | (ui < jnp.exp(-(cand - cur) / temp))
            A = jnp.where(accept[:, None], cand_A, A)
            cur = jnp.where(accept, cand, cur)
            if P:
                # Even/odd neighbor swap pass: parity alternates the
                # pairing; the last pair is clipped to a self-pair
                # (valid=False) on odd ladders.
                lo = 2 * p_ar + par
                hi = lo + 1
                valid = hi < M
                lo_c = jnp.minimum(lo, M - 1)
                hi_c = jnp.minimum(hi, M - 1)
                delta = (1.0 / temps[lo_c] - 1.0 / temps[hi_c]) * (
                    cur[lo_c] - cur[hi_c]
                )
                sw = valid & (sui < jnp.exp(delta))
                A_lo, A_hi = A[lo_c], A[hi_c]
                c_lo, c_hi = cur[lo_c], cur[hi_c]
                A = A.at[lo_c].set(jnp.where(sw[:, None], A_hi, A_lo))
                A = A.at[hi_c].set(jnp.where(sw[:, None], A_lo, A_hi))
                cur = cur.at[lo_c].set(jnp.where(sw, c_hi, c_lo))
                cur = cur.at[hi_c].set(jnp.where(sw, c_lo, c_hi))
            m_star = jnp.argmin(cur)
            step_best = cur[m_star]
            better = step_best < best
            best = jnp.where(better, step_best, best)
            best_a = jnp.where(better, A[m_star], best_a)
            return (A, cur, best_a, best), step_best

        A0 = jnp.broadcast_to(init_a, (M, init_a.shape[0]))
        cur0 = _objective_rows(Vc, capsc, steps_d, w_d, comps_d, A0)
        m0 = jnp.argmin(cur0)
        (A, cur, best_a, best), hist = jax.lax.scan(
            step,
            (A0, cur0, A0[m0], cur0[m0]),
            (
                jnp.swapaxes(t_idx, 0, 1),
                jnp.swapaxes(s_idx, 0, 1),
                jnp.swapaxes(u, 0, 1),
                su,
                parity,
            ),
        )
        return best_a, best, jnp.concatenate([cur0[m0][None], hist])

    # vmap chains inside candidates: stream cells are (C, K, M, iters)
    # and swap uniforms (C, K, iters, P); V/caps/init vary per candidate,
    # the ladder, tenant tables, and parity schedule are shared.
    per_chain = jax.vmap(
        _one_ladder,
        in_axes=(None, None, None, None, None, None, None, 0, 0, 0, 0,
                 None),
    )
    fn = jax.jit(jax.vmap(
        per_chain,
        in_axes=(0, 0, None, None, None, 0, None, 0, 0, 0, 0, None),
    ))
    _GRID_PROGRAMS[key] = fn
    return fn


class ChainKernel:
    """K annealing chains over a pre-priced strategy pool, on device.

    ``V[t, s, :]`` is tenant ``t``'s cluster-level link-load vector under
    pool strategy ``s`` (priced once on host by the bit-exact NumPy
    evaluator); a chain state is one pool index per tenant.  Each scan step
    re-prices the proposed state *from scratch* — gather T rows, sum, one
    bottleneck division — so chain objectives carry no incremental float
    lineage, and the batched chains match the sequential NumPy reference to
    reassociation level.

    ``objective="union"`` anneals on the union bottleneck comm time (the
    historical jobset objective); ``objective="decomposed"`` anneals on the
    weighted per-tenant decomposed comm times
    (:func:`~repro.core.strategy_search.tenant_comm_times` semantics:
    each tenant's own bytes under weighted processor sharing of every link
    it loads).

    **Grid mode** (``V.ndim == 4``): ``V[ci, t, s, :]`` stacks one load
    tensor per placement candidate, padded to the widest candidate's link
    table (dummy links carry zero load against ``caps[ci, pad:]``, so they
    can never win a bottleneck); ``caps`` becomes ``(C, L)``.  Each chain
    then carries a whole parallel-tempering ladder: every scan step applies
    the annealing rule to all ``M`` rungs at once, follows with a
    deterministic even/odd neighbor swap pass (Metropolis swap acceptance
    ``su < exp((1/T_lo - 1/T_hi) * (E_lo - E_hi))`` on pre-drawn host
    uniforms, iteration parity alternating the pairing), and tracks the
    per-(candidate, chain) best state across rungs — the whole
    (candidate x chain x rung) grid in **one** jit dispatch
    (:meth:`run_grid`).  A singleton ladder performs no swap pass and
    replays the flat kernel's decisions exactly.
    """

    def __init__(
        self,
        V: np.ndarray,  # (T, S, L) load vectors; (C, T, S, L) = grid mode
        caps: np.ndarray,  # (L,); (C, L) in grid mode
        comps: np.ndarray,  # (T,) per-tenant compute times
        weights: np.ndarray,  # (T,) tenant weights
        overlap: float = 0.0,
        objective: str = "union",
        steps: np.ndarray | None = None,  # (T, S) latency rounds per entry
        alpha: float = 0.0,  # per-round link latency (hw.link_latency)
    ):
        jax = _require_jax()
        jnp = jax.numpy
        if objective not in ("union", "decomposed"):
            raise ValueError(f"unknown chain objective {objective!r}")
        self.objective = objective
        self.grid = V.ndim == 4
        if self.grid:
            self._init_grid(V, caps, comps, weights, overlap, objective,
                            steps, alpha)
            return
        T, S, L = V.shape
        self.shape = (T, S, L)
        V_d = jnp.asarray(V, dtype=jnp.float64)
        caps_d = jnp.asarray(caps, dtype=jnp.float64)
        comps_d = jnp.asarray(comps, dtype=jnp.float64)
        w_d = jnp.asarray(weights, dtype=jnp.float64)
        total_w = float(np.sum(weights))
        t_arange = jnp.arange(T)
        alpha = float(alpha)
        steps_d = (
            jnp.asarray(steps, dtype=jnp.float64)
            if steps is not None and alpha
            else None
        )

        def _objective(a):
            rows = V_d[t_arange, a]  # (T, L)
            if objective == "union":
                comm = jnp.max(rows.sum(axis=0) / caps_d)
                if steps_d is not None:
                    # Union latency rounds = the worst tenant's rounds
                    # (remap/union preserve group sizes and pinned steps).
                    comm = comm + alpha * jnp.max(steps_d[t_arange, a])
                comm_t = jnp.full((T,), comm)
            else:
                active = rows > 0.0
                active_w = jnp.sum(
                    jnp.where(active, w_d[:, None], 0.0), axis=0
                )  # (L,) contending weight per link
                per = jnp.where(
                    active,
                    rows * active_w[None, :]
                    / (w_d[:, None] * caps_d[None, :]),
                    0.0,
                )
                comm_t = jnp.max(per, axis=1)
                if steps_d is not None:
                    comm_t = comm_t + alpha * steps_d[t_arange, a]
            hidden = jnp.minimum(comm_t * overlap, comps_d)
            iters_t = comps_d + comm_t - hidden
            return jnp.sum(w_d * iters_t) / total_w

        def _one_chain(init_a, temperature, t_idx, s_idx, u):
            def step(carry, inp):
                a, cur, best_a, best = carry
                ti, si, ui = inp
                cand_a = a.at[ti].set(si)
                cand = _objective(cand_a)
                temp = temperature * jnp.maximum(cur, 1e-12)
                accept = (cand <= cur) | (
                    ui < jnp.exp(-(cand - cur) / temp)
                )
                a = jnp.where(accept, cand_a, a)
                cur = jnp.where(accept, cand, cur)
                better = accept & (cand < best)
                best_a = jnp.where(better, cand_a, best_a)
                best = jnp.where(better, cand, best)
                return (a, cur, best_a, best), cur

            cur0 = _objective(init_a)
            (a, cur, best_a, best), hist = jax.lax.scan(
                step, (init_a, cur0, init_a, cur0), (t_idx, s_idx, u)
            )
            return best_a, best, jnp.concatenate([cur0[None], hist])

        self._run = jax.jit(
            jax.vmap(_one_chain, in_axes=(None, 0, 0, 0, 0))
        )
        self._objective_np = None  # built on demand for the reference path

    def run(
        self,
        init_a: np.ndarray,  # (T,) shared start state
        temperatures: np.ndarray,  # (K,) per-chain temperature
        t_idx: np.ndarray,  # (K, iters)
        s_idx: np.ndarray,
        u: np.ndarray,
    ):
        """All K chains in one dispatch.  Returns
        ``(best_assignments (K, T), best_objs (K,), history (K, iters+1))``
        as NumPy arrays."""
        if self.grid:
            raise ValueError("grid-mode ChainKernel runs via run_grid()")
        jnp = _require_jax().numpy
        best_a, best, hist = self._run(
            jnp.asarray(init_a, dtype=jnp.int64),
            jnp.asarray(temperatures, dtype=jnp.float64),
            jnp.asarray(t_idx, dtype=jnp.int64),
            jnp.asarray(s_idx, dtype=jnp.int64),
            jnp.asarray(u, dtype=jnp.float64),
        )
        return (
            np.asarray(best_a),
            np.asarray(best, dtype=np.float64),
            np.asarray(hist, dtype=np.float64),
        )

    def _init_grid(self, V, caps, comps, weights, overlap, objective,
                   steps, alpha):
        jnp = _require_jax().numpy
        C, T, S, L = V.shape
        caps = np.asarray(caps, dtype=np.float64)
        if caps.shape != (C, L):
            raise ValueError(
                f"grid caps must have shape {(C, L)}, got {caps.shape}"
            )
        self.shape = (T, S, L)
        self.grid_shape = (C, T, S, L)
        self._V_g = jnp.asarray(V, dtype=jnp.float64)
        self._caps_g = jnp.asarray(caps, dtype=jnp.float64)
        self._comps_g = jnp.asarray(comps, dtype=jnp.float64)
        self._w_g = jnp.asarray(weights, dtype=jnp.float64)
        alpha = float(alpha)
        self._steps_g = (
            jnp.asarray(steps, dtype=jnp.float64)
            if steps is not None and alpha
            else None
        )
        # The compiled grid program is shared across kernel instances
        # (keyed by the scalar parameters, shape-specialized by jit), so
        # rebuilding the kernel every alternating round costs no
        # recompile as long as the padded grid shapes repeat.
        self._run_grid_fn = _grid_program(
            objective, float(overlap), alpha, float(np.sum(weights)),
            self._steps_g is not None,
        )

    def run_grid(
        self,
        init_a: np.ndarray,  # (C, T) per-candidate start states
        temperatures: np.ndarray,  # (M,) ascending tempering ladder
        t_idx: np.ndarray,  # (C, K, M, iters)
        s_idx: np.ndarray,
        u: np.ndarray,
        swap_u: np.ndarray,  # (C, K, iters, M // 2)
        device: bool = False,
    ):
        """The whole (candidate x chain x rung) grid in one dispatch.

        Returns ``(best_assignments (C, K, T), best_objs (C, K),
        history (C, K, iters + 1))`` — history is the running
        min-over-rungs objective.  ``device=True`` returns the raw JAX
        arrays so callers (the fused alternating loop) can hand the winner
        indices straight back into the next round's dispatch without a
        host round-trip.
        """
        if not self.grid:
            raise ValueError("flat ChainKernel runs via run()")
        jax = _require_jax()
        jnp = jax.numpy
        iters = t_idx.shape[3]
        parity = jnp.asarray(np.arange(iters, dtype=np.int64) % 2)
        best_a, best, hist = self._run_grid_fn(
            self._V_g,
            self._caps_g,
            self._comps_g,
            self._w_g,
            self._steps_g,
            jnp.asarray(init_a, dtype=jnp.int64),
            jnp.asarray(temperatures, dtype=jnp.float64),
            jnp.asarray(t_idx, dtype=jnp.int64),
            jnp.asarray(s_idx, dtype=jnp.int64),
            jnp.asarray(u, dtype=jnp.float64),
            jnp.asarray(swap_u, dtype=jnp.float64),
            parity,
        )
        if device:
            return best_a, best, hist
        return (
            np.asarray(best_a),
            np.asarray(best, dtype=np.float64),
            np.asarray(hist, dtype=np.float64),
        )


def _objective_reference(
    V: np.ndarray,
    caps: np.ndarray,
    comps: np.ndarray,
    weights: np.ndarray,
    overlap: float,
    objective: str,
    a: np.ndarray,
    steps: np.ndarray | None = None,
    alpha: float = 0.0,
) -> float:
    """NumPy mirror of :class:`ChainKernel`'s on-device objective."""
    T = V.shape[0]
    rows = V[np.arange(T), a]
    if objective == "union":
        comm = np.max(rows.sum(axis=0) / caps)
        if steps is not None and alpha:
            comm = comm + alpha * np.max(steps[np.arange(T), a])
        comm_t = np.full(T, comm)
    else:
        active = rows > 0.0
        active_w = np.where(active, weights[:, None], 0.0).sum(axis=0)
        per = np.where(
            active,
            rows * active_w[None, :] / (weights[:, None] * caps[None, :]),
            0.0,
        )
        comm_t = per.max(axis=1)
        if steps is not None and alpha:
            comm_t = comm_t + alpha * steps[np.arange(T), a]
    hidden = np.minimum(comm_t * overlap, comps)
    iters_t = comps + comm_t - hidden
    return float(np.sum(weights * iters_t) / np.sum(weights))


def _run_cell_or_grid(
    V, caps, comps, weights, overlap, objective, steps, alpha,
    seed, chains, iters, T, S, temperature, temperatures,
):
    """Dispatch one jobset search: the flat K-chain kernel when no ladder
    is requested, the C=1 grid kernel under a tempering ladder.  Returns
    ``(best_a (K', T), best_obj (K',), hist (K', iters + 1))`` with the
    grid's candidate axis squeezed away."""
    if temperatures is None:
        kernel = ChainKernel(
            V, caps, comps, weights, overlap=overlap, objective=objective,
            steps=steps, alpha=alpha,
        )
        t_idx, s_idx, u = draw_proposal_streams(seed, chains, iters, T, S)
        return kernel.run(
            np.zeros(T, dtype=np.int64),
            np.full(chains, temperature, dtype=np.float64),
            t_idx, s_idx, u,
        )
    ladder = np.asarray(check_temper_ladder(temperatures), dtype=np.float64)
    M = ladder.size
    kernel = ChainKernel(
        V[None], np.asarray(caps, dtype=np.float64)[None], comps, weights,
        overlap=overlap, objective=objective, steps=steps, alpha=alpha,
    )
    t_idx, s_idx, u = draw_grid_streams(seed, 1, chains, M, iters, T, S)
    su = draw_swap_streams(seed, 1, chains, M, iters)
    best_a, best_obj, hist = kernel.run_grid(
        np.zeros((1, T), dtype=np.int64), ladder, t_idx, s_idx, u, su,
    )
    return best_a[0], best_obj[0], hist[0]


def jax_mcmc_search(
    job,
    topo,
    hw: HardwareSpec,
    iters: int = 200,
    temperature: float = 0.1,
    overlap: float = 0.0,
    seed: int = 0,
    init=None,
    chains: int = 1,
    pool_size: int = 64,
    schedules=None,
    temperatures=None,
):
    """Batched single-job strategy search — the ``backend="jax"`` body of
    :func:`~repro.core.strategy_search.mcmc_search`.

    The pool's load vectors are priced once on host by the bit-exact
    evaluator; all ``chains`` annealing chains then run in one device
    dispatch (:class:`ChainKernel` with one tenant).  The winning
    strategy's reported ``iter_time`` is re-priced on the NumPy path, so
    result values carry no device float lineage; ``history`` is the best
    chain's on-device objective trace.  ``schedules`` widens the pool with
    collective-schedule flips; with ``hw.link_latency`` set the chains
    anneal on the same (α, β) objective the NumPy path prices.

    ``temperatures`` replaces the single ``temperature`` with a
    parallel-tempering ladder run through the grid kernel — a singleton
    ladder ``(t,)`` replays the flat ``temperature=t`` chains' decisions
    exactly (same proposal streams, no swap draws).
    """
    from .demand import demand_steps
    from .netsim import _iteration_time as iteration_time, compute_time
    from .strategy_search import SearchResult

    n = topo.n
    pool = strategy_pool(
        job, n, pool_size, seed + _POOL_SEED_OFFSET, init=init,
        schedules=schedules,
    )
    ev = plan_evaluator(topo, hw)
    demands = [s.demand(job, n) for s in pool]
    vecs = [ev.loads(d) for d in demands]  # grows the link universe
    L = ev.n_links
    S = len(pool)
    V = np.zeros((1, S, max(L, 1)), dtype=np.float64)
    for s, v in enumerate(vecs):
        V[0, s, : v.size] = v
    caps = ev.caps if L else np.ones(1)
    comp = compute_time(job.flops_per_sample * job.batch_per_gpu * n, n, hw)
    steps = (
        np.asarray([[demand_steps(d) for d in demands]], dtype=np.float64)
        if hw.link_latency
        else None
    )
    best_a, best_obj, hist = _run_cell_or_grid(
        V, caps, np.array([comp]), np.array([1.0]), overlap, "union",
        steps, hw.link_latency, seed, chains, iters, 1, S,
        temperature, temperatures,
    )
    c = int(np.argmin(best_obj))
    strategy = pool[int(best_a[c, 0])]
    demand = demands[int(best_a[c, 0])]
    iter_time = iteration_time(ev.comm_time(demand), comp, overlap=overlap)
    return SearchResult(
        strategy=strategy, iter_time=iter_time, demand=demand,
        history=[float(h) for h in hist[c]],
    )


def jax_mcmc_search_jobset(
    jobset,
    topo,
    hw: HardwareSpec,
    iters: int = 200,
    temperature: float = 0.1,
    overlap: float = 0.0,
    seed: int = 0,
    init=None,
    chains: int = 1,
    pool_size: int = 64,
    objective: str = "union",
    demand_cache=None,
    schedules=None,
    temperatures=None,
):
    """Batched multi-tenant strategy search — the ``backend="jax"`` body of
    :func:`~repro.core.strategy_search.mcmc_search_jobset`.

    Per tenant, a pool of ``pool_size`` candidate strategies is priced once
    into cluster-level link-load vectors (through the incremental
    evaluator's caches, so repeat pricings are shared with the NumPy path);
    ``chains`` chains of per-tenant pool moves then anneal in one dispatch
    under the requested objective.  The winner's reported
    ``iter_time``/``per_job`` are re-priced on the bit-exact NumPy path
    (union) or the reference decomposition (decomposed).

    ``temperatures`` swaps the single ``temperature`` for a
    parallel-tempering ladder through the grid kernel; the singleton
    ladder replays the flat kernel's decisions exactly.
    """
    from .netsim import compute_time
    from .planeval import JobSetEvaluator, LRUCache
    from .strategy_search import (
        JobSetSearchResult,
        demand_cache_size,
        default_strategy,
        evaluate_jobset,
        evaluate_jobset_decomposed,
    )

    if not jobset.tenants:
        raise ValueError("jax_mcmc_search_jobset needs at least one tenant")
    if demand_cache is None:
        demand_cache = LRUCache(demand_cache_size())
    jse = JobSetEvaluator(
        jobset, topo, hw, overlap=overlap, demand_cache=demand_cache
    )
    tenants = jobset.tenants
    T = len(tenants)
    init = init or {}
    pools = []
    for i, t in enumerate(tenants):
        start = init.get(t.label) or default_strategy(t.spec)
        pools.append(strategy_pool(
            t.spec, t.k, pool_size, seed + _POOL_SEED_OFFSET + i,
            init=start, schedules=schedules,
        ))
    # Price every pool entry first (the link universe grows as new MP
    # routes are compiled), then pad all vectors to the final width.
    vecs = [
        [jse.tenant_loads_at(t.label, s, t.servers) for s in pools[i]]
        for i, t in enumerate(tenants)
    ]
    L = jse.ev.n_links
    S = pool_size
    V = np.zeros((T, S, max(L, 1)), dtype=np.float64)
    for i in range(T):
        for s, v in enumerate(vecs[i]):
            V[i, s, : v.size] = v
    caps = jse.ev.caps if L else np.ones(1)
    comps = np.array([
        compute_time(t.flops_per_iteration, t.k, hw) for t in tenants
    ])
    weights = np.array([t.weight for t in tenants], dtype=np.float64)
    steps = None
    if hw.link_latency:
        # Per-(tenant, pool entry) latency rounds of the tenant's *local*
        # embedded demand — union rounds are the max over tenants, which
        # the kernel's union objective takes per chain state.
        steps = np.asarray([
            [jse._steps(t.label, s) for s in pools[i]]
            for i, t in enumerate(tenants)
        ], dtype=np.float64)
    best_a, best_obj, hist = _run_cell_or_grid(
        V, caps, comps, weights, overlap, objective, steps,
        hw.link_latency, seed, chains, iters, T, S,
        temperature, temperatures,
    )
    c = int(np.argmin(best_obj))
    best = {
        t.label: pools[i][int(best_a[c, i])] for i, t in enumerate(tenants)
    }
    if objective == "decomposed":
        obj, per_job = evaluate_jobset_decomposed(
            best, jobset, topo, hw, overlap, _demand_cache=demand_cache
        )
        union = jse.union_for(best)
    else:
        obj, union, per_job = evaluate_jobset(
            best, jobset, topo, hw, overlap,
            _demand_cache=demand_cache, compiled=True,
        )
    return JobSetSearchResult(
        strategies=best, iter_time=obj, demand=union, per_job=per_job,
        history=[float(h) for h in hist[c]],
    )


def pack_jobset_grid(
    candidates,  # list[JobSet]: same tenants, different placements
    topos,  # list[Topology], one search topology per candidate
    hw: HardwareSpec,
    pools,  # list[list[Strategy]], one pre-built pool per tenant
    overlap: float = 0.0,
    demand_cache=None,
    pad_cap: float = 1.0,
    pad_to: int = 32,
):
    """Stack per-candidate pool pricings into the padded grid tensors.

    Each candidate's pool entries are priced on its own topology through
    the incremental :class:`~repro.core.planeval.JobSetEvaluator` (one
    shared per-tenant demand cache serves all candidates — job-local
    demands are placement-independent), then every candidate's link table
    is padded to the widest one: dummy links carry zero load against
    capacity ``pad_cap``, so they can never win a bottleneck max nor
    activate in the decomposed objective, whatever ``pad_cap > 0`` is.

    ``pad_to`` additionally rounds the link axis up to a bucket multiple
    so the grid shape repeats across alternating rounds (and admissions of
    similar size) — repeated shapes hit the shared compiled grid program's
    jit cache instead of recompiling per round.

    Returns ``(V (C, T, S, L), caps (C, L), comps (T,), weights (T,),
    steps (T, S) | None, evaluators)``.
    """
    from .netsim import compute_time
    from .planeval import JobSetEvaluator, LRUCache
    from .strategy_search import demand_cache_size

    if demand_cache is None:
        demand_cache = LRUCache(demand_cache_size())
    labels = [t.label for t in candidates[0].tenants]
    for js in candidates:
        if [t.label for t in js.tenants] != labels:
            raise ValueError(
                "grid candidates must list the same tenants in the same "
                "order"
            )
    evs = []
    vecs_per = []
    for js, topo in zip(candidates, topos):
        jse = JobSetEvaluator(
            js, topo, hw, overlap=overlap, demand_cache=demand_cache
        )
        # Price every entry before reading n_links: the link universe
        # grows as new MP routes compile.
        vecs = [
            [jse.tenant_loads_at(t.label, s, t.servers) for s in pools[i]]
            for i, t in enumerate(js.tenants)
        ]
        evs.append(jse)
        vecs_per.append(vecs)
    C, T, S = len(candidates), len(labels), len(pools[0])
    L = max(max(jse.ev.n_links for jse in evs), 1)
    if pad_to > 1:
        L = -(-L // pad_to) * pad_to
    V = np.zeros((C, T, S, L), dtype=np.float64)
    caps = np.full((C, L), float(pad_cap), dtype=np.float64)
    for ci, (jse, vecs) in enumerate(zip(evs, vecs_per)):
        nl = jse.ev.n_links
        if nl:
            caps[ci, :nl] = jse.ev.caps
        for i in range(T):
            for s, v in enumerate(vecs[i]):
                V[ci, i, s, : v.size] = v
    tenants = candidates[0].tenants
    comps = np.array(
        [compute_time(t.flops_per_iteration, t.k, hw) for t in tenants]
    )
    weights = np.array([t.weight for t in tenants], dtype=np.float64)
    steps = None
    if hw.link_latency:
        # Latency rounds are placement-independent (group sizes and pinned
        # steps survive remapping), so one candidate's table serves all.
        steps = np.asarray([
            [evs[0]._steps(t.label, s) for s in pools[i]]
            for i, t in enumerate(tenants)
        ], dtype=np.float64)
    return V, caps, comps, weights, steps, evs


def run_chains_reference(
    V: np.ndarray,
    caps: np.ndarray,
    comps: np.ndarray,
    weights: np.ndarray,
    overlap: float,
    objective: str,
    init_a: np.ndarray,
    temperatures: np.ndarray,
    t_idx: np.ndarray,
    s_idx: np.ndarray,
    u: np.ndarray,
    steps: np.ndarray | None = None,
    alpha: float = 0.0,
):
    """Sequential NumPy replay of the batched chains: same pre-drawn
    streams, same annealing rule, one chain at a time — the equivalence
    oracle ``tests/test_planeval_jax.py`` pins the device kernel against."""
    K, iters = t_idx.shape
    T = V.shape[0]
    best_as = np.zeros((K, T), dtype=np.int64)
    bests = np.zeros(K, dtype=np.float64)
    hists = np.zeros((K, iters + 1), dtype=np.float64)
    for c in range(K):
        a = np.array(init_a, dtype=np.int64)
        cur = _objective_reference(
            V, caps, comps, weights, overlap, objective, a,
            steps=steps, alpha=alpha,
        )
        best_a, best = a.copy(), cur
        hists[c, 0] = cur
        for i in range(iters):
            cand_a = a.copy()
            cand_a[t_idx[c, i]] = s_idx[c, i]
            cand = _objective_reference(
                V, caps, comps, weights, overlap, objective, cand_a,
                steps=steps, alpha=alpha,
            )
            temp = temperatures[c] * max(cur, 1e-12)
            if cand <= cur or u[c, i] < math.exp(-(cand - cur) / temp):
                a, cur = cand_a, cand
                if cand < best:
                    best_a, best = cand_a.copy(), cand
            hists[c, i + 1] = cur
        best_as[c] = best_a
        bests[c] = best
    return best_as, bests, hists


def _swap_pass_reference(
    A: np.ndarray,  # (M, T) ladder states, mutated in place
    cur: np.ndarray,  # (M,) ladder energies, mutated in place
    temps: np.ndarray,  # (M,) ascending ladder
    su: np.ndarray,  # (M // 2,) swap uniforms of this iteration
    parity: int,
):
    """One even/odd neighbor swap pass — the host mirror of the grid
    kernel's tempering exchange (same clipping of the out-of-range last
    pair, same Metropolis swap acceptance)."""
    M = cur.shape[0]
    for p in range(M // 2):
        lo = 2 * p + parity
        hi = lo + 1
        if hi >= M:
            continue
        delta = (1.0 / temps[lo] - 1.0 / temps[hi]) * (cur[lo] - cur[hi])
        # exp saturates above ~709; any delta past ~50 already accepts
        # with certainty against a uniform < 1 (the device side computes
        # exp(delta) = inf, which accepts identically).
        if su[p] < math.exp(min(delta, 50.0)):
            A[[lo, hi]] = A[[hi, lo]]
            cur[lo], cur[hi] = cur[hi], cur[lo]
    return A, cur


def run_grid_reference(
    V: np.ndarray,  # (C, T, S, L)
    caps: np.ndarray,  # (C, L)
    comps: np.ndarray,
    weights: np.ndarray,
    overlap: float,
    objective: str,
    init_a: np.ndarray,  # (C, T)
    temperatures: np.ndarray,  # (M,)
    t_idx: np.ndarray,  # (C, K, M, iters)
    s_idx: np.ndarray,
    u: np.ndarray,
    swap_u: np.ndarray,  # (C, K, iters, M // 2)
    steps: np.ndarray | None = None,
    alpha: float = 0.0,
):
    """Sequential NumPy replay of the fused (candidate x chain x rung)
    grid: one cell at a time, same pre-drawn streams, same per-rung
    annealing rule, same even/odd swap passes — the equivalence oracle the
    property tests pin :meth:`ChainKernel.run_grid` against."""
    C, K, M, iters = t_idx.shape
    T = V.shape[1]
    temps = np.asarray(temperatures, dtype=np.float64)
    best_as = np.zeros((C, K, T), dtype=np.int64)
    bests = np.zeros((C, K), dtype=np.float64)
    hists = np.zeros((C, K, iters + 1), dtype=np.float64)

    def obj(ci, a):
        return _objective_reference(
            V[ci], caps[ci], comps, weights, overlap, objective, a,
            steps=steps, alpha=alpha,
        )

    for ci in range(C):
        for c in range(K):
            A = np.tile(init_a[ci].astype(np.int64), (M, 1))
            cur = np.array([obj(ci, A[m]) for m in range(M)])
            m0 = int(np.argmin(cur))
            best_a, best = A[m0].copy(), cur[m0]
            hists[ci, c, 0] = cur[m0]
            for i in range(iters):
                for m in range(M):
                    cand_a = A[m].copy()
                    cand_a[t_idx[ci, c, m, i]] = s_idx[ci, c, m, i]
                    cand = obj(ci, cand_a)
                    temp = temps[m] * max(cur[m], 1e-12)
                    if cand <= cur[m] or u[ci, c, m, i] < math.exp(
                        -(cand - cur[m]) / temp
                    ):
                        A[m] = cand_a
                        cur[m] = cand
                if M > 1:
                    _swap_pass_reference(
                        A, cur, temps, swap_u[ci, c, i], i % 2
                    )
                m_star = int(np.argmin(cur))
                if cur[m_star] < best:
                    best_a, best = A[m_star].copy(), cur[m_star]
                hists[ci, c, i + 1] = cur[m_star]
            best_as[ci, c] = best_a
            bests[ci, c] = best
    return best_as, bests, hists
