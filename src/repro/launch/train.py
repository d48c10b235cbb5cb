"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --steps 200 --ckpt-dir /tmp/ckpt

On this CPU container use ``--smoke`` (reduced config).  On a pod, drop
``--smoke`` and pass ``--mesh single|multi`` to train the full config on the
production mesh with the plan flags below.
"""

from __future__ import annotations

import argparse

import jax

from repro.configs.base import ShapeSpec, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw, cosine, wsd
from repro.parallel.sharding import ShardingPlan
from repro.train.loop import train


def main() -> None:
    ap = argparse.ArgumentParser(description="TopoOpt training driver")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["cpu", "single", "multi"], default="cpu")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--fail-at", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    shape = ShapeSpec("cli", args.seq_len, args.global_batch, "train")

    if args.mesh == "cpu":
        mesh = jax.make_mesh((jax.device_count(),), ("data",))
        plan = ShardingPlan(fsdp=False, remat=args.remat)
    else:
        from .mesh import make_production_mesh

        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        plan = ShardingPlan(
            fsdp=not args.no_fsdp, seq_parallel=args.seq_parallel,
            remat=args.remat,
        )

    sched = (wsd if cfg.schedule == "wsd" else cosine)(args.lr, args.steps)
    res = train(
        cfg, shape, adamw(sched), plan, mesh,
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, fail_at=args.fail_at,
    )
    print(
        f"done: step={res.final_step} loss {res.losses[0]:.4f} -> "
        f"{res.losses[-1]:.4f} stragglers={res.straggler_steps}"
    )


if __name__ == "__main__":
    main()
