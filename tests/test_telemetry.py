"""The program's recorder (``repro.telemetry``) and the spans, counters and
named scopes of the training runtime: the data pipeline, the recorded step
and the loop."""

import re
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.data.pipeline as pipeline
from repro import telemetry
from repro.configs.base import ShapeSpec, get_config, input_specs
from repro.data.pipeline import DataSpec, Prefetcher
from repro.models import lm
from repro.optim import adamw, cosine
from repro.parallel.sharding import ShardingPlan
from repro.train import loop
from repro.train.steps import RecordedStep, jit_train_step, make_shardmap_dp_train_step

SMOKE = get_config("granite-8b").smoke()
SHAPE = ShapeSpec("tiny", seq_len=32, global_batch=4, kind="train")
SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


def _in_thread(fn, timeout=30.0):
    """Run ``fn`` on a thread of its own; fail rather than hang."""
    out = {}

    def run():
        try:
            out["value"] = fn()
        except Exception as e:  # handed back to the test
            out["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "blocked"
    return out


def test_ring_is_bounded_at_100k_spans():
    rec = telemetry.Recorder()
    for i in range(100_000):
        with rec.span("t.tick", i):
            pass
    kept = rec.recent("t.tick", 10**6)
    assert len(kept) == telemetry.KEEP == 8192
    assert [r.step for r in kept[-3:]] == [99_997, 99_998, 99_999]
    assert kept[0].step == 100_000 - 8192
    assert all(r.end_ns >= r.start_ns for r in kept)
    assert rec.recent("t.tick", 0) == [] and rec.recent("t.none", 5) == []


def test_parents_and_counters_in_the_innermost_span():
    rec = telemetry.Recorder()
    with rec.span("a", 1) as a:
        rec.count("n")
        with rec.span("b") as b:
            with rec.span("c") as c:
                rec.count("n", 2)
                rec.count("m")
            rec.count("n", 4)
    rec.count("n", 8)  # no open span: counted nowhere
    assert (a.parent, b.parent, c.parent) == (None, "a", "b")
    assert a.counts == {"n": 1} and b.counts == {"n": 4}
    assert c.counts == {"n": 2, "m": 1}
    assert [r.counts for r in rec.recent("a", 5)] == [{"n": 1}]
    assert a.step == 1 and b.step is None
    assert a.start_ns <= b.start_ns <= c.start_ns <= c.end_ns <= b.end_ns <= a.end_ns


def test_threads_keep_their_own_spans():
    rec = telemetry.Recorder()
    with rec.span("main.outer") as outer:
        def other():
            with rec.span("other") as o:
                rec.count("k")
            rec.count("k")
            return o

        o = _in_thread(other)["value"]
    assert o.parent is None and o.counts == {"k": 1}
    assert outer.counts == {}
    assert o.thread != outer.thread == threading.get_ident()


def test_prefetcher_spans_worker_records_apart():
    """The worker thread making batches records nothing; each wait is the
    consumer's, inside the consumer's own span."""
    spec = DataSpec(cfg=SMOKE, shape=SHAPE, seed=0)
    t0 = time.perf_counter_ns()
    pf = Prefetcher(spec, start_step=5, depth=2)
    try:
        with telemetry.span("test.consumer"):
            got = [pf.next()[0] for _ in range(3)]
    finally:
        pf.close()
    assert got == [5, 6, 7]
    waits = [w for w in telemetry.recent("data.wait", 64) if w.start_ns >= t0]
    assert [w.step for w in waits] == [5, 6, 7]
    assert {(w.parent, w.thread) for w in waits} == {
        ("test.consumer", threading.get_ident())}
    assert pf._thread.ident not in {
        r.thread for ring in telemetry.RECORDER._rings.values() for r in ring
        if r.start_ns >= t0}


def test_prefetcher_raises_the_worker_error(monkeypatch):
    real = pipeline.batch_for_step

    def failing(spec, step):
        if step == 7:
            raise ValueError(f"no batch for step {step}")
        return real(spec, step)

    monkeypatch.setattr(pipeline, "batch_for_step", failing)
    pf = Prefetcher(DataSpec(cfg=SMOKE, shape=SHAPE, seed=0), start_step=5)
    try:
        assert _in_thread(lambda: [pf.next()[0] for _ in range(2)])["value"] == [5, 6]
        for _ in range(2):  # and again: it never blocks
            err = _in_thread(pf.next)["error"]
            assert isinstance(err, ValueError) and "step 7" in str(err)
    finally:
        pf.close()


def _batch(seq_len):
    return pipeline.batch_for_step(
        DataSpec(cfg=SMOKE, shape=ShapeSpec("t", seq_len, 4, "train")), 0)


def _state(opt, p_sh, o_sh):
    """Parameters and optimizer state placed as the step returns them, so
    that only a new batch length can build a new executable."""
    params = jax.jit(lambda: lm.init(jax.random.PRNGKey(0), SMOKE),
                     out_shardings=p_sh)()
    return params, jax.jit(opt.init, out_shardings=o_sh)(params)


@pytest.mark.parametrize("style", ["jit", "shard_map"])
def test_recorded_step_counts_a_recompile_per_new_length(style):
    mesh = jax.make_mesh((1,), ("data",))
    opt = adamw(cosine(1e-3, 10))
    if style == "jit":
        step, (_p, _o, p_sh, o_sh, _b) = jit_train_step(
            SMOKE, opt, ShardingPlan(fsdp=False), mesh)
        params, state = _state(opt, p_sh, o_sh)

        def call(i, batch):
            with mesh:
                return step(params, state, batch, jnp.int32(i))[:2]
    else:
        step = make_shardmap_dp_train_step(SMOKE, opt, mesh)
        # Replicated, spelled as the step's outputs spell it.
        full = lambda tree: jax.tree.map(  # noqa: E731
            lambda x: NamedSharding(mesh, P(*[None] * x.ndim)), tree)
        p_specs = lm.param_specs(SMOKE)
        params, state = _state(opt, full(p_specs),
                               full(jax.eval_shape(opt.init, p_specs)))

        def call(i, batch):
            return step(params, state, batch, jnp.int32(i), 0)[:2]

    assert isinstance(step, RecordedStep)
    for i, seq in enumerate((32, 32, 16, 16)):
        before = params
        params, state = call(i, _batch(seq))
    got = telemetry.recent("train.dispatch", 4)
    assert [r.counts.get("train.compiles") for r in got] == [1, 0, 1, 0]
    # The calls that traced the step also counted its attention calls,
    # which take the XLA path on the CPU.
    assert [r.counts.get("attention.xla", 0) > 0 for r in got] == [
        True, False, True, False]
    assert not any("attention.fused" in r.counts for r in got)
    assert all(set(r.counts) <= {"train.compiles", "attention.xla"} for r in got)
    assert all(r.end_ns > r.start_ns for r in got)
    # Donation is the jit's: the jitted step consumes its inputs.
    leaf = jax.tree.leaves(before)[0]
    assert leaf.is_deleted() == (style == "jit")


def test_recorded_step_lowers_with_the_named_scopes():
    mesh = jax.make_mesh((1,), ("data",))
    opt = adamw(cosine(1e-3, 10))
    step, (p_specs, o_specs, _psh, _osh, _b) = jit_train_step(
        SMOKE, opt, ShardingPlan(fsdp=False), mesh)
    lowered = step.lower(p_specs, o_specs, input_specs(SMOKE, SHAPE),
                         jax.ShapeDtypeStruct((), jnp.int32))
    text = lowered.as_text(debug_info=True)
    for scope in ("jvp(embed)", "jvp(blocks)", "jvp(head_loss)",
                  "transpose(jvp(blocks))", "transpose(jvp(head_loss))",
                  "/adamw/"):
        assert scope in text, scope
    assert step._cache_size() == 0  # lowering builds no executable


def test_loop_spans_recompile_log_and_straggler(monkeypatch):
    real_batch = pipeline.batch_for_step
    real_build = loop.jit_train_step

    def short_at_6(spec, step):
        b = real_batch(spec, step)
        return {"tokens": b["tokens"][:, :16]} if step == 6 else b

    class SlowAt9(RecordedStep):
        def __call__(self, params, state, batch, i):
            if int(i) == 9:
                time.sleep(0.5)
            return super().__call__(params, state, batch, i)

    def slow_at_9(*a, **kw):
        step, rest = real_build(*a, **kw)
        return SlowAt9(step._jitted), rest

    monkeypatch.setattr(pipeline, "batch_for_step", short_at_6)
    monkeypatch.setattr(loop, "jit_train_step", slow_at_9)
    logs = []
    res = loop.train(SMOKE, SHAPE, adamw(cosine(1e-3, 60)),
                     ShardingPlan(fsdp=False), jax.make_mesh((1,), ("data",)),
                     total_steps=12, log_every=100, logger=logs.append)
    assert res.final_step == 12 and np.all(np.isfinite(res.losses))
    assert [m for m in logs if "built" in m] == [
        "[loop] step 6 built 1 executable(s)"]
    assert any(m.startswith("[loop] straggler at step 9:") for m in logs)
    assert res.straggler_steps >= 1
    steps = telemetry.recent("train.step", 12)
    assert [r.step for r in steps] == list(range(12))
    assert all(r.parent is None for r in steps)
    for name in ("train.loss_read", "train.dispatch"):
        inner = telemetry.recent(name, 12)
        assert all(r.parent == "train.step" for r in inner), name
    assert [r.step for r in telemetry.recent("train.loss_read", 12)] == list(range(12))


def test_no_program_span_carries_the_benchmark_prefix():
    """The benchmark names idle gaps by its own ``bench:`` spans alone."""
    names = set()
    for path in SRC.rglob("*.py"):
        names.update(re.findall(r'telemetry\.span\(\s*"([^"]+)"', path.read_text()))
    assert {"data.wait", "train.dispatch", "train.step",
            "train.loss_read"} <= names
    assert not any(n.startswith("bench:") for n in names)
    assert not telemetry.TRACE_PREFIX.startswith("bench:")
