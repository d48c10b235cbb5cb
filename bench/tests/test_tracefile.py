"""The reduction from a profiler trace to busy time, program time,
collective time and the breakdown."""

from types import SimpleNamespace as NS

import pytest

from bench import tracefile


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def line(name, events):
    return NS(name=name, events=events)


def device(idx, ops, modules=()):
    return NS(name=f"/device:TPU:{idx}",
              lines=[line("XLA Modules", list(modules)), line("XLA Ops", ops)])


def host(spans):
    return NS(name="/host:CPU", lines=[line("python", spans)])


def test_busy_union_programs_and_gaps():
    planes = [
        host([ev("bench:window", 1000, 10000), ev("bench:probe", 2500, 3000),
              ev("other", 0, 99999)]),
        device(0, [ev("fusion.1", 1000, 1000), ev("fusion.2", 1500, 1000),
                   ev("collective-permute.3", 6000, 500),
                   ev("fusion.1", 9000, 5000)],
               [ev("jit__one_ladder(12)", 1000, 1500), ev("jit_step", 6000, 500)]),
        device(1, [ev("fusion.1", 1000, 2000)]),
        device(4, [ev("fusion.9", 1000, 9000)]),  # not a chip of this cell
    ]
    s = tracefile.summarize(planes, chips=2)
    assert s.window_s == pytest.approx(10e-6)
    # chip 0: [1000, 2500] + [6000, 6500] + [9000, 11000 clipped] = 4000 ns;
    # chip 1: 2000 ns.
    assert s.busy_s == pytest.approx(3000e-9)
    assert s.chips == 2
    assert s.program_s("_one_ladder") == pytest.approx(1500e-9)
    assert s.collective_permute_s == pytest.approx(500e-9)
    assert s.idle_percent() == pytest.approx(70.0)
    gaps = dict(s.idle_gaps)
    assert gaps["probe"] == pytest.approx(3500e-9)  # 2500..6000, mid in probe
    assert gaps["host"] == pytest.approx(2500e-9)  # 6500..9000
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["device_ops"][0][1] == pytest.approx(3000e-9)
    assert len(b["idle_gaps"]) <= 10


def test_nothing_to_read_gives_none():
    assert tracefile.summarize([host([ev("bench:window", 0, 10)])], 1) is None
    assert tracefile.summarize([device(0, [ev("f", 0, 5)])], 1) is None


def test_recorded_trace_is_read(tmp_path):
    """A real .xplane.pb recorded on the CPU: the window span is found and
    a trace with no accelerator plane reduces to nothing."""
    import jax
    import jax.numpy as jnp

    from bench.spans import Spans, Tracer

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = Tracer(True, str(tmp_path / "trace"))
    spans = Spans()
    tracer.start()
    with spans.span("probe"):
        f(x).block_until_ready()
    tracer.stop()
    path = tracefile.find_xplane(str(tmp_path / "trace"))
    from jax.profiler import ProfileData

    names = {e.name for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events}
    assert {"bench:window", "bench:probe"} <= names
    assert tracefile.summarize_file(path, 1) is None
    assert spans.count["probe"] == 1 and spans.total["probe"] > 0
