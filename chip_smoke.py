"""Bring-up run of the system's main paths on one TPU chip.

    python chip_smoke.py               # Phase A, then Phase B, on one chip
    python chip_smoke.py --four-chips  # TotientPerms gradient sync, 4 chips

Phase A trains minicpm-2b at its published widths, with its depth cut to
what one 16 GB chip holds, through ``repro.train.loop.train``: the path
``python -m repro.launch.train`` takes.  Phase B replays a churn trace on
the paper's 432-server shared cluster (section 5) through
``run_online_jobset`` with the JAX planner backend, so every admission runs
the fused candidate x tempering-ladder grid on the device.  ``--four-chips``
runs only the gradient-sync path that exists across chips: the three
TotientPerms all-reduce kernels and the schedule-synced training step, each
against ``lax.psum``.

Times printed along the way come from one bring-up run; they are not
benchmark measurements.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``.  The script exits non-zero and prints no
result when JAX finds no TPU or when any phase fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import ShapeSpec, get_config  # noqa: E402
from repro.core.collectives import (  # noqa: E402
    multi_ring_all_reduce,
    multi_tree_all_reduce,
    recursive_hd_all_reduce,
)
from repro.core.device_order import topoopt_mesh  # noqa: E402
from repro.core.netsim import HardwareSpec  # noqa: E402
from repro.core.online import (  # noqa: E402
    JobSetController,
    ReoptPolicy,
    TraceEvent,
    run_online_jobset,
)
from repro.core.planeval_jax import DEFAULT_TEMPER_LADDER, ChainKernel  # noqa: E402
from repro.core.strategy_search import evaluate_jobset  # noqa: E402
from repro.core.workloads import BERT, CANDLE, DLRM, VGG16, JobSet, TenantJob  # noqa: E402
from repro.data.pipeline import DataSpec, batch_for_step  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.optim import adamw, wsd  # noqa: E402
from repro.parallel.sharding import ShardingPlan  # noqa: E402
from repro.train.loop import train  # noqa: E402
from repro.train.steps import make_shardmap_dp_train_step  # noqa: E402

MODEL = "minicpm-2b"
# Depth cut: 10 of 40 layers.  Compiled for one v5e chip, the train step's
# arguments plus temporaries take 13.45 GiB at 10 layers, 14.37 at 11 and
# 15.32 at 12, against 16 GiB of HBM (bf16 params, fp32 AdamW moments and
# master); 10 is the deepest that leaves 1.5 GiB for the runtime.
TRAIN_LAYERS = 10
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 1024, 2, 5
# The shard_map step keeps params and optimizer state replicated and does
# not donate them, so two copies live at once on each chip: 2 layers.
SYNC_LAYERS = 2
SYNC_STEPS = 3
SYNC_BYTES = (4 << 10, 1 << 20, 32 << 20)  # per device: latency to bandwidth

# The paper's shared cluster (section 5, bench_shared): 432 servers of
# degree 8 with 100 Gbps links, 16-server tenants in the paper's mix.  The
# resident set is cut from ~10 tenants to 6: the host-side fluid simulator
# bounds the trace (every replan simulates two iterations), and 10
# residents take ~2.2x as long as 6, too close to a 20-minute run.
CLUSTER = dict(n=432, degree=8, job_size=16, resident=6, arrivals=4,
               departures=2, failures=2, n_iters=8)
MIX = ((DLRM, 0.4), (BERT, 0.3), (CANDLE, 0.2), (VGG16, 0.1))


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _on(arrays, device) -> bool:
    return all(a.devices() == {device} for a in jax.tree.leaves(arrays))


def phase_train(cfg, *, seq_len: int, batch: int, steps: int, log=print):
    """A few AdamW steps of ``cfg`` on a one-device mesh through
    ``repro.train.loop.train``.  Every loss must be finite, and the params
    and the loss must live on the first device."""
    device = jax.devices()[0]
    mesh = jax.make_mesh((1,), ("data",), devices=[device])
    plan = ShardingPlan(fsdp=False, remat="full")
    t0 = time.perf_counter()
    res = train(
        cfg, ShapeSpec("smoke", seq_len, batch, "train"),
        adamw(wsd(3e-3, steps)), plan, mesh, total_steps=steps,
        log_every=1, logger=log,
    )
    wall = time.perf_counter() - t0
    _check(len(res.losses) == steps, f"ran {len(res.losses)} of {steps} steps")
    _check(all(math.isfinite(x) for x in res.losses),
           f"non-finite loss: {res.losses}")
    _check(_on(res.params, device), "params are not on the device")
    _check(_on(res.metrics["loss"], device), "loss is not on the device")
    stats = device.memory_stats() or {}
    return dict(layers=cfg.n_layers, seq_len=seq_len, batch=batch,
                losses=res.losses, wall_s=wall,
                peak_bytes=stats.get("peak_bytes_in_use"))


def _shared_cluster(n, job_size, resident, seed):
    rng = np.random.default_rng(seed)
    jobs = [job for job, _ in MIX]
    p = [frac for _, frac in MIX]

    def draw():
        return jobs[rng.choice(len(jobs), p=p)]

    tenants = [
        TenantJob(spec=draw(), name=f"r{i}",
                  servers=tuple(range(i * job_size, (i + 1) * job_size)))
        for i in range(resident)
    ]
    return JobSet(n=n, tenants=tenants), draw, rng


def phase_planner(*, n: int, degree: int, job_size: int, resident: int,
                  arrivals: int, departures: int, failures: int,
                  n_iters: int, seed: int = 0, log=print):
    """A seeded churn trace on a shared cluster through
    ``run_online_jobset`` with the fused JAX admission path.

    Checks that every grid dispatch ran on the first device, that at least
    one admission went through the fused candidate grid, that no optimizer
    error, deadline overrun or refusal occurred, that the final plan passes
    validation on the degraded fabric, and that its ``iter_time`` re-prices
    exactly on the NumPy evaluator."""
    device = jax.devices()[0]
    hw = HardwareSpec(link_bandwidth=12.5e9, degree=degree)
    jobset, draw, rng = _shared_cluster(n, job_size, resident, seed)
    policy = ReoptPolicy.reactive(
        backend="jax", chains=4, temperatures=DEFAULT_TEMPER_LADDER,
        candidates=4,
    )

    dispatches = []
    run_grid = ChainKernel.run_grid

    def traced_run_grid(self, *args, device=False, **kw):
        t0 = time.perf_counter()
        out = run_grid(self, *args, device=device, **kw)
        jax.block_until_ready(out)
        placed = (out[1] if device else self._V_g).devices()
        dispatches.append((self.grid_shape, placed, device,
                           time.perf_counter() - t0))
        return out

    ChainKernel.run_grid = traced_run_grid
    try:
        t0 = time.perf_counter()
        plan = JobSetController(jobset, hw=hw, policy=policy,
                                seed=seed).ensure_plan()
        t_plan = time.perf_counter() - t0
        # Fibers that fail: edges of the initial plan between servers of
        # tenants that stay for the whole trace.
        staying = {s for t in jobset.tenants[departures:] for s in t.servers}
        edges = sorted({
            (min(a, b), max(a, b)) for a, b in plan.topology.graph.edges()
            if a in staying and b in staying
        })
        dead = [edges[i] for i in
                rng.choice(len(edges), size=failures, replace=False)]
        queues = {
            "arrive": [TraceEvent(0, "arrive", job=draw(), k=job_size,
                                  name=f"a{i}") for i in range(arrivals)],
            "fail": [TraceEvent(0, "fail", link=e) for e in dead],
            "depart": [TraceEvent(0, "depart", name=t.label)
                       for t in jobset.tenants[:departures]],
        }
        order = []
        while any(queues.values()):
            for q in queues.values():
                if q:
                    order.append(q.pop(0))
        trace = tuple(
            dataclasses.replace(ev, iteration=1 + i * (n_iters - 1) // len(order))
            for i, ev in enumerate(order)
        )
        t0 = time.perf_counter()
        result = run_online_jobset(jobset, hw, policy=policy, trace=trace,
                                   n_iters=n_iters, seed=seed, plan=plan)
        t_run = time.perf_counter() - t0
    finally:
        ChainKernel.run_grid = run_grid

    faults = [r.trigger for r in result.log
              if r.trigger.endswith((":error", ":deadline"))]
    _check(not faults, f"optimizer faults: {faults}")
    _check(not result.refused, f"refused arrivals: {result.refused}")
    _check(result.n_replans >= 1, "no replan was adopted")
    fused = [d for d in dispatches if d[0][0] > 1]
    _check(bool(fused), "no admission ran the fused candidate grid")
    _check(all(d[1] == {device} for d in dispatches),
           f"grid dispatches off {device}: {[d[1] for d in dispatches]}")
    admitted = [r for r in result.log
                if r.trigger == "arrival" and r.replanned]
    _check(bool(admitted), "no arrival replan was adopted")

    final = result.final_plan
    validator = JobSetController(result.final_jobset, hw=hw, plan=final)
    validator.dead.update(dead)
    violations = validator.plan_violations(final.topology)
    _check(not violations, f"final plan violations: {violations}")
    repriced, _, _ = evaluate_jobset(final.strategies, final.jobset,
                                     final.topology, hw)
    _check(repriced == final.iter_time,
           f"final plan re-prices to {repriced!r}, not {final.iter_time!r}")

    times = [d[3] for d in fused]
    return dict(
        servers=n, tenants_final=len(result.final_jobset.tenants),
        events=len(trace), replans=result.n_replans,
        arrivals_adopted=len(admitted), optimizer_errors=len(faults),
        grid_dispatches=len(dispatches), fused_dispatches=len(fused),
        widest_grid=max(d[0] for d in fused),
        grid_s=sum(d[3] for d in dispatches),
        first_fused_s=times[0], later_fused_median_s=(
            float(np.median(times[1:])) if len(times) > 1 else None),
        initial_plan_s=t_plan, trace_s=t_run,
        final_iter_time=final.iter_time,
    )


def _sync_mesh():
    devices = jax.devices()
    _check(len(devices) >= 4, f"need 4 devices, found {len(devices)}")
    return topoopt_mesh((4,), ("data",), devices=np.asarray(devices[:4]))


def four_chip_allreduce(sizes, *, seed: int = 0, log=print):
    """The three TotientPerms all-reduce kernels against ``lax.psum`` on a
    4-device mesh.  Inputs hold small integers in f32, so every summation
    order gives the same bits and the comparison is exact."""
    mesh = _sync_mesh()
    shard = NamedSharding(mesh, P("data"))
    kernels = {
        "multi_ring(1,3)": lambda v: multi_ring_all_reduce(v, "data", (1, 3)),
        "recursive_hd": lambda v: recursive_hd_all_reduce(v, "data"),
        "multi_tree(1,3)": lambda v: multi_tree_all_reduce(v, "data", (1, 3)),
    }

    def smap(fn):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data"), check_vma=False))

    psum = smap(lambda v: lax.psum(v, "data"))
    kernels = {name: smap(fn) for name, fn in kernels.items()}
    rows = []
    for nbytes in sizes:
        make = jax.jit(
            lambda key, n=nbytes // 4: jax.random.randint(
                key, (4, n), -1000, 1000).astype(jnp.float32),
            out_shardings=shard,
        )
        x = make(jax.random.PRNGKey(seed))
        _check(_spread(x, mesh), f"input of {nbytes} B is not on 4 devices")
        ref = psum(x)
        for name, fn in kernels.items():
            out = fn(x)
            _check(_spread(out, mesh), f"{name} output is not on 4 devices")
            same = bool(jnp.array_equal(out, ref))
            _check(same, f"{name} differs from lax.psum at {nbytes} B")
            rows.append(dict(kernel=name, bytes=nbytes, equal=same))
        log(f"[four-chip] {nbytes} B/device: all kernels == lax.psum")
    return rows


def _spread(x, mesh) -> bool:
    want = set(mesh.devices.flat)
    return ({s.device for s in x.addressable_shards} == want
            and x.addressable_shards[0].data.shape[0] == x.shape[0] // 4)


def four_chip_train(cfg, *, seq_len: int, steps: int, seed: int = 0,
                    log=print):
    """``make_shardmap_dp_train_step`` synced by each TotientPerms schedule
    against the ``lax.psum`` step, from the same params and batches.
    Losses must agree to the tolerance ``tests/test_system.py`` holds the
    schedules to: their sums round in another order."""
    mesh = _sync_mesh()
    spec = DataSpec(cfg=cfg, shape=ShapeSpec("sync", seq_len, 4, "train"),
                    seed=seed)
    batches = [
        jax.device_put(batch_for_step(spec, i), NamedSharding(mesh, P("data")))
        for i in range(steps)
    ]
    _check(all(_spread(b["tokens"], mesh) for b in batches),
           "batch is not sharded over 4 devices")
    rep = NamedSharding(mesh, P())
    out = {}
    for name, strides, schedule in (
        ("psum", (), "ring"),
        ("multi_ring(1,3)", (1, 3), "ring"),
        ("recursive_hd", (1, 3), "recursive_hd"),
        ("multi_tree(1,3)", (1, 3), "multi_tree"),
    ):
        opt = adamw(wsd(3e-3, steps))
        step = make_shardmap_dp_train_step(
            cfg, opt, mesh, ring_strides=strides, schedule=schedule)
        params = jax.jit(lambda: lm.init(jax.random.PRNGKey(seed), cfg),
                         out_shardings=rep)()
        state = jax.jit(opt.init, out_shardings=rep)(params)
        losses = []
        for i, batch in enumerate(batches):
            params, state, loss, _ = step(params, state, batch,
                                          jnp.int32(i), 0)
            losses.append(float(loss))
        _check(all(math.isfinite(x) for x in losses),
               f"{name}: non-finite loss {losses}")
        del params, state
        out[name] = losses
        log(f"[four-chip] {name} losses {losses}")
    ref = out["psum"]
    for name, losses in out.items():
        _check(np.allclose(losses, ref, rtol=1e-3, atol=1e-4),
               f"{name} losses {losses} != psum {ref}")
    return out


def _device_line():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip gradient-sync path")
    args = ap.parse_args(argv)

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    dev = _device_line()
    print(f"[smoke] bring-up run on {dev['count']} x {dev['kind']}; "
          "times below are from this one run, not benchmark numbers")
    base = get_config(MODEL)

    if args.four_chips:
        _check(dev["count"] >= 4, f"--four-chips needs 4 chips, found "
               f"{dev['count']}")
        cfg = dataclasses.replace(base, n_layers=SYNC_LAYERS)
        print(f"[four-chip] {MODEL} at published widths, {SYNC_LAYERS} of "
              f"{base.n_layers} layers")
        four_chip_allreduce(SYNC_BYTES)
        four_chip_train(cfg, seq_len=TRAIN_SEQ, steps=SYNC_STEPS)
    else:
        cfg = dataclasses.replace(base, n_layers=TRAIN_LAYERS)
        print(f"[A] {MODEL} at published widths, depth cut to "
              f"{TRAIN_LAYERS} of {base.n_layers} layers; seq "
              f"{TRAIN_SEQ} x batch {TRAIN_BATCH}")
        a = phase_train(cfg, seq_len=TRAIN_SEQ, batch=TRAIN_BATCH,
                        steps=TRAIN_STEPS)
        print(f"[A] losses {a['losses']}; wall {a['wall_s']:.1f} s "
              f"(compile included); peak HBM {a['peak_bytes']} B")
        b = phase_planner(**CLUSTER)
        print("[B] " + json.dumps(b, default=str))
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
