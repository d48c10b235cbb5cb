"""Training steps of a decoder through the pieces ``repro.train.loop.train``
uses: the program's jitted step (``repro.train.steps.jit_train_step``,
parameters and optimizer state donated), its AdamW, and its prefetching
data pipeline (``repro.data.pipeline.Prefetcher``), with the loss read
back after every step.

Set-up builds the one step and its state, makes the weights on the device
from the seed, and drives the first ``check_steps`` steps through the
window's own call and feed; those steps compile the program and give the
readings the check compares.  The window then continues the same steps
for ``--seconds``.

The check follows the same steps with the plain float32 reference
(``bench/ref/minicpm.py``) on the same weights and batches and compares
each step's loss, the first gradient as AdamW holds it after one step, and
the parameters' change after the first steps, both as per-leaf norms.
"""

from __future__ import annotations

import math
import time

from bench import gen
from bench.core import Compared, Window
from bench.ref import minicpm

# Limits of the one-chip cell; PERF.md gives the readings they were set
# from.
LIMITS = {"loss_gap": 3e-4, "grad_gap": 2.5e-3, "change_gap": 0.02}
# Readings of ``calibration()`` that have to come out correct; every other
# one (the control, the faults) has to fail.
MUST_PASS = ("program",)


def model_flops_per_token(dm: dict, seq_len: int) -> float:
    """Operations a forward and backward pass need per token, without
    recomputation: 6 per weight of every matmul (the tied head counts once,
    the embedding lookup not at all) plus causal attention's score and
    value products, 6 * 2 * S * heads * head_dim / 2 per layer."""
    d, L, F, V = dm["d"], dm["layers"], dm["ff"], dm["vocab"]
    q, kv = dm["heads"] * dm["hd"], dm["kv_heads"] * dm["hd"]
    matmul = L * (d * q + 2 * d * kv + q * d + 3 * d * F) + d * V
    attention = L * 6 * seq_len * dm["heads"] * dm["hd"]
    return 6.0 * matmul + attention


def program_config(config: dict):
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name="bench", family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype=config["param_dtype"],
        activation_dtype=config["activation_dtype"],
    )


def program_optimizer(opt: dict):
    from repro.optim import adamw, wsd

    return adamw(
        wsd(opt["peak_lr"], opt["total_steps"], warmup_frac=opt["warmup_frac"],
            decay_frac=opt["decay_frac"], final_frac=opt["final_frac"]),
        b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"], master_fp32=opt["master_fp32"],
    )


def leaf_norms(tree, scale: float = 1.0):
    import jax
    import jax.numpy as jnp

    return [jnp.linalg.norm(x.astype(jnp.float32)) * scale
            for x in jax.tree.leaves(tree)]


def change_norms(tree, p0_host) -> list[float]:
    """Per-leaf norm of ``tree`` minus the initial weights, on the host, so
    that the check adds nothing to the device's memory."""
    import jax
    import numpy as np

    out = []
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(p0_host)):
        d = (np.asarray(jax.device_get(a), np.float32)
             - np.asarray(b, np.float32)).ravel()
        out.append(float(np.sqrt(np.dot(d, d))))
    return out


class Runner:
    limits = LIMITS

    def __init__(self, cell, devices, seed: int, spans, log):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.devices = devices
        self.seed = seed
        self.spans = spans
        self.log = log
        self.dm = minicpm.dims(self.config)
        self.window_losses: list[float] = []

    def build(self):
        """The jitted step, its parameter and optimizer-state shardings,
        and the mesh."""
        import jax

        from repro.parallel.sharding import ShardingPlan
        from repro.train.steps import jit_train_step

        mesh = jax.make_mesh((1,), ("data",), devices=self.devices[:1])
        plan = ShardingPlan(fsdp=self.config["sharding"]["fsdp"],
                            remat=self.config["sharding"]["remat"])
        jitted, (p_specs, _o, p_sh, o_sh, _b) = jit_train_step(
            self.cfg, self.opt, plan, mesh, donate=True)
        return jitted, p_specs, p_sh, o_sh, mesh

    def put_batch(self, batch):
        return batch

    def call_step(self, step_i: int, batch):
        import jax.numpy as jnp

        with self.mesh:
            self.params, self.opt_state, metrics = self.step(
                self.params, self.opt_state, self.put_batch(batch),
                jnp.int32(step_i))
        return metrics["loss"]

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        import jax

        from repro.configs.base import ShapeSpec
        from repro.data.pipeline import DataSpec, Prefetcher

        t_setup = time.perf_counter()
        self.cfg = program_config(self.config)
        self.opt = program_optimizer(self.config["optimizer"])
        self.step, p_specs, p_sh, o_sh, self.mesh = self.build()
        self.key = minicpm.key_for(self.seed)
        init = jax.jit(lambda k: minicpm.init_params(k, self.dm),
                       out_shardings=p_sh)
        self.params = init(self.key)
        # The initial weights, kept on the host: the parameters' change and
        # the reference both start from these very bits.
        self.p0 = jax.device_get(self.params)
        self.log(f"weights made {time.perf_counter() - t_setup:.3f} s")
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), p_specs)
        if got != want:
            raise RuntimeError(f"weights {got} do not match the step's {want}")
        self.opt_state = jax.jit(self.opt.init, out_shardings=o_sh)(self.params)
        S, B = self.traffic["seq_len"], self.traffic["batch"]
        self.data_seed = abs(int(self.seed))
        self.data = Prefetcher(
            DataSpec(cfg=self.cfg, shape=ShapeSpec("bench", S, B, "train"),
                     seed=self.data_seed),
            start_step=0, depth=self.traffic["prefetch_depth"])
        b1 = self.config["optimizer"]["b1"]
        grad_norms = jax.jit(lambda m: leaf_norms(m, 1.0 / (1.0 - b1)))
        self.paths = minicpm.leaf_paths(p_specs)
        self.check_losses = []
        n = self.traffic["check_steps"]
        for i in range(n):
            t0 = time.perf_counter()
            got_i, batch = self.data.next()
            if got_i != i:
                raise RuntimeError(f"pipeline gave step {got_i}, not {i}")
            self.check_losses.append(float(self.call_step(i, batch)))
            self.log(f"step {i} loss {self.check_losses[-1]!r} "
                     f"({time.perf_counter() - t0:.3f} s)")
            if i == 0:
                self.prog_grad = dict(zip(self.paths, map(float, grad_norms(
                    self.opt_state["m"]))))
        self.log(f"first steps done {time.perf_counter() - t_setup:.3f} s")
        source = self.opt_state.get("master", self.params)
        self.prog_change = dict(zip(self.paths, change_norms(
            source, self.p0)))
        self.next_step = n

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float, tracer) -> Window:
        S, B = self.traffic["seq_len"], self.traffic["batch"]
        trace_for = self.traffic["trace_seconds"]
        steps = traced = 0
        tracer.start()
        t0 = time.perf_counter()
        # The untraced part of the window: all of it without --trace 1,
        # else the steps after the profiler has stopped and written its
        # trace, timed from then on.
        t_free, free_from = (None, 0) if tracer.started else (t0, 0)
        try:
            while True:
                with self.spans.span("data_wait"):
                    got_i, batch = self.data.next()
                if got_i != self.next_step:
                    raise RuntimeError(f"pipeline gave step {got_i}, not {self.next_step}")
                with self.spans.span("step"):
                    loss = float(self.call_step(self.next_step, batch))
                self.window_losses.append(loss)
                self.next_step += 1
                steps += 1
                now = time.perf_counter() - t0
                if tracer.started and not tracer.stopped:
                    traced += 1
                    if now >= trace_for:
                        tracer.stop()
                        t_free, free_from = time.perf_counter(), steps
                if now >= seconds:
                    break
        finally:
            t_end = time.perf_counter()
            elapsed = t_end - t0
            tracer.stop()
        tokens = steps * B * S
        extra = {"flops_per_token": model_flops_per_token(self.dm, S)}
        if t_free is not None and steps > free_from:
            extra["untraced_tokens_per_s"] = (
                (steps - free_from) * B * S / (t_end - t_free))
        self.log(f"steps {steps}, last loss {self.window_losses[-1]!r}")
        return Window(
            elapsed=elapsed, units=steps, unit_name="steps",
            e2e={"tokens_per_s": tokens / elapsed}, traced_units=traced,
            extra=extra,
        )

    def release(self) -> None:
        self.data.close()
        self.params = self.opt_state = None

    # -- check ----------------------------------------------------------------

    def reference(self, quantize: bool = False, rows=None):
        """Losses, first-step gradient norms and change norms of the
        reference over the check steps.  ``rows`` keeps only those batch
        rows (a fault: part of the batch left out)."""
        import jax

        n = self.traffic["check_steps"]
        S, B = self.traffic["seq_len"], self.traffic["batch"]
        ref = minicpm.Reference(self.dm, self.config["optimizer"], quantize)
        p0 = jax.device_put(self.p0, self.devices[0])
        params = minicpm.Reference.from_program_layout(p0)
        batches = [gen.token_batch(self.data_seed, i, B, S, self.dm["vocab"])
                   for i in range(n)]
        if rows is not None:
            batches = [b[rows] for b in batches]
        losses, grads = ref.train(params, batches, n)
        change = minicpm.change_norms_ref(params, p0)
        return losses, grads, change

    def readings(self, prog, ref) -> dict:
        losses, grads, change = ref
        moving = minicpm.moving_leaves(grads)
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog[0], losses))
        grad_gap, grad_at = minicpm.worst_leaf_gap(prog[1], grads)
        change_gap, change_at = minicpm.worst_leaf_gap(prog[2], change, moving)
        return {"loss_gap": loss_gap, "grad_gap": grad_gap,
                "change_gap": change_gap, "grad_at": grad_at,
                "change_at": change_at, "losses": list(prog[0]),
                "ref_losses": list(losses)}

    def program_readings(self):
        return self.check_losses, self.prog_grad, self.prog_change

    def fault_rows(self) -> dict:
        """Faults planted in the reference, by the batch rows they keep:
        half of the batch left out."""
        return {"half_batch": list(range(self.traffic["batch"] // 2))}

    def calibration(self) -> dict:
        """Readings of the program, of the control (the reference with
        int8 matmul inputs in its place) and of the faults planted in the
        reference."""
        ref = self.reference()
        out = {
            "program": self.readings(self.program_readings(), ref),
            "control": self.readings(self.reference(quantize=True), ref),
        }
        for name, rows in self.fault_rows().items():
            out[name] = self.readings(self.reference(rows=rows), ref)
        return out

    def compare(self, r: dict) -> list[Compared]:
        return [Compared(name, r[name], limit)
                for name, limit in self.limits.items()]

    def check(self):
        r = self.readings(self.program_readings(), self.reference())
        self.log(f"losses {r['losses']} reference {r['ref_losses']}; worst "
                 f"gradient leaf {r['grad_at']}, worst change leaf "
                 f"{r['change_at']}")
        failed = sum(not math.isfinite(x) for x in self.window_losses)
        return self.compare(r), len(self.window_losses), failed
