"""Fault-injection subsystem: transient faults, correlated domains,
partition survival, and the hardened replan path.

* Construction-time validation: unknown ``TraceEvent`` kinds and
  repair-before-failure ``LinkFailure``\\ s raise instead of being skipped.
* Engine: transient repairs restore capacity byte-preservingly; partition
  survival accounts downtime / restarts / availability; checkpoint-restore
  restart costs block resumed jobs; the fault-free path carries no fault
  state.
* :class:`repro.core.faults.FaultModel`: seeded determinism, per-pair
  outage merging, correlated-domain atomicity, substream stability.
* Controller: ``repair`` restores the degraded incumbent in place,
  candidate plans are validated before adoption, optimizer crash storms
  exhaust a bounded retry budget and back off instead of wedging, and
  unhostable arrivals are refused gracefully.
* Property tests (hypothesis or the seeded shim): random transient storms
  conserve bytes, fail/repair interleavings keep degree budgets, and the
  heap and dense max-min fills stay bit-identical through fail -> repair
  round trips.
"""

from __future__ import annotations

import os

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.alternating import alternating_optimize
from repro.core.costmodel import (
    CHECKPOINT_RESTORE_BW,
    MIGRATION_RESTART_S,
    checkpoint_restart_s,
)
from repro.core.faults import FaultModel, server_domain, stride_domain
from repro.core.netsim import HardwareSpec
from repro.core.online import (
    JobSetController,
    ReoptController,
    ReoptPolicy,
    TraceEvent,
    place_arrival,
    run_online,
)
from repro.core.simengine import LinkFailure, Scenario, SimEngine, SimJob, Task
from repro.core.workloads import DLRM, VGG16, JobSet, TenantJob

HW = HardwareSpec(link_bandwidth=12.5e9, degree=4)


@pytest.fixture(scope="module")
def vgg_plan():
    return alternating_optimize(VGG16, 8, HW, rounds=1, mcmc_iters=10, seed=0)


def _flow_job(name, nbytes=1000.0, route=(0, 1)):
    return SimJob(name, [Task(tid=0, kind="flow", nbytes=nbytes, route=route)])


# ---------------------------------------------------------------------------
# Construction-time validation (satellite: no silently skipped events)
# ---------------------------------------------------------------------------


def test_trace_event_unknown_kind_raises():
    with pytest.raises(ValueError, match="unknown TraceEvent kind"):
        TraceEvent(iteration=0, kind="faii", link=(0, 1))
    with pytest.raises(ValueError, match="unknown TraceEvent kind"):
        TraceEvent(iteration=0, kind="Fail", link=(0, 1))


def test_trace_event_fail_and_repair_require_link():
    with pytest.raises(ValueError, match="requires a link"):
        TraceEvent(iteration=0, kind="fail")
    with pytest.raises(ValueError, match="requires a link"):
        TraceEvent(iteration=0, kind="repair")
    TraceEvent(iteration=0, kind="load")  # load/arrive/depart need no link


def test_link_failure_repair_must_follow_failure():
    with pytest.raises(ValueError, match="strictly after"):
        LinkFailure(time=5.0, link=(0, 1), repair_time=5.0)
    with pytest.raises(ValueError, match="strictly after"):
        LinkFailure(time=5.0, link=(0, 1), repair_time=4.0)
    LinkFailure(time=5.0, link=(0, 1), repair_time=5.0 + 1e-9)


# ---------------------------------------------------------------------------
# Engine: transient repair + partition survival
# ---------------------------------------------------------------------------


def test_transient_fault_byte_preserving_restore():
    """No surviving path: the flow waits out the outage, then finishes with
    its remaining bytes intact."""
    r = SimEngine().run(Scenario(
        links={(0, 1): 100.0},
        jobs=[_flow_job("j")],
        failures=(LinkFailure(time=5.0, link=(0, 1), repair_time=7.0),),
        n=2,
    ))
    assert r.delivered["j"] == 1000.0
    assert r.makespan == pytest.approx(12.0, rel=1e-5)
    assert r.downtime["j"] == pytest.approx(2.0, rel=1e-9)
    assert r.restarts == {"j": 1}
    assert r.availability("j") == pytest.approx(10.0 / 12.0, rel=1e-5)
    assert r.goodput["j"] == pytest.approx(1000.0 / r.makespan, rel=1e-9)


def test_transient_fault_with_detour_reroutes_then_restores():
    """A surviving detour carries the bytes during the outage; the repair
    re-paths multi-hop flows back."""
    r = SimEngine().run(Scenario(
        links={(0, 1): 100.0, (0, 2): 100.0, (2, 1): 100.0},
        jobs=[_flow_job("j")],
        failures=(LinkFailure(time=5.0, link=(0, 1), repair_time=7.0),),
        n=3,
    ))
    assert not r.stalled
    assert r.delivered["j"] == 1000.0
    assert r.makespan == pytest.approx(10.0, rel=1e-5)  # detour at full rate
    assert r.downtime.get("j", 0.0) == 0.0  # never actually dark
    assert r.restarts == {}


def test_partition_survival_accounting():
    """Jobs inside a surviving component run degraded; cross-partition jobs
    stall, accrue downtime, and pay a checkpoint-restore restart."""
    links = {(0, 1): 100.0, (1, 0): 100.0, (1, 2): 100.0,
             (2, 1): 100.0, (2, 3): 100.0, (3, 2): 100.0}
    r = SimEngine().run(Scenario(
        links=links, n=4,
        jobs=[_flow_job("local", route=(0, 1)),
              _flow_job("cross", route=(1, 2))],
        failures=(LinkFailure(time=2.0, link=(1, 2), repair_time=6.0),),
        restart_s={"cross": 1.0},
    ))
    assert r.delivered == {"local": 1000.0, "cross": 1000.0}
    assert r.availability("local") == 1.0
    assert r.job_finish["local"] == pytest.approx(10.0, rel=1e-5)
    # 4 s dark (t=2..6) + 1 s checkpoint-restore restart pause.
    assert r.downtime == {"cross": pytest.approx(5.0, rel=1e-9)}
    assert r.restarts == {"cross": 1}
    assert r.job_finish["cross"] == pytest.approx(15.0, rel=1e-5)
    assert r.availability("cross") == pytest.approx(2.0 / 3.0, rel=1e-4)


def test_restart_cost_defaults_to_instant_resume():
    """Without Scenario.restart_s the restart is counted but free."""
    links = {(0, 1): 100.0, (1, 0): 100.0, (1, 2): 100.0,
             (2, 1): 100.0, (2, 3): 100.0, (3, 2): 100.0}
    r = SimEngine().run(Scenario(
        links=links, n=4,
        jobs=[_flow_job("cross", route=(1, 2))],
        failures=(LinkFailure(time=2.0, link=(1, 2), repair_time=6.0),),
    ))
    assert r.restarts == {"cross": 1}
    assert r.downtime["cross"] == pytest.approx(4.0, rel=1e-9)
    assert r.job_finish["cross"] == pytest.approx(14.0, rel=1e-5)


def test_fault_free_run_carries_no_fault_state():
    r = SimEngine().run(Scenario(
        links={(0, 1): 100.0}, jobs=[_flow_job("j")], n=2,
    ))
    assert r.downtime == {} and r.restarts == {}
    assert r.availability("j") == 1.0
    assert r.goodput["j"] == pytest.approx(1000.0 / r.makespan, rel=1e-9)


# ---------------------------------------------------------------------------
# Checkpoint-restore cost helper
# ---------------------------------------------------------------------------


def test_checkpoint_restart_s():
    assert checkpoint_restart_s(0.0) == MIGRATION_RESTART_S
    assert checkpoint_restart_s(CHECKPOINT_RESTORE_BW) == pytest.approx(
        MIGRATION_RESTART_S + 1.0)
    assert checkpoint_restart_s(1e9, checkpoint_bw=1e9, restart_s=2.0) == 3.0
    with pytest.raises(ValueError):
        checkpoint_restart_s(-1.0)


def test_jobset_restart_costs_match_helper():
    js = JobSet(n=6, tenants=[
        TenantJob(spec=DLRM, servers=(0, 1), name="d"),
        TenantJob(spec=VGG16, servers=(2, 3), name="v"),
    ])
    costs = js.restart_costs()
    assert costs == {
        "d": checkpoint_restart_s(DLRM.state_bytes),
        "v": checkpoint_restart_s(VGG16.state_bytes),
    }


# ---------------------------------------------------------------------------
# FaultModel: seeded storms
# ---------------------------------------------------------------------------

_PAIRS = ((0, 1), (1, 2), (2, 3), (0, 3))


def _model(seed=0, **kw):
    kw.setdefault("link_mtbf", 10.0)
    kw.setdefault("link_mttr", 2.0)
    return FaultModel(n=4, links=_PAIRS, seed=seed, **kw)


def test_fault_model_is_deterministic():
    a, b = _model(seed=5), _model(seed=5)
    assert a.link_failures(200.0) == b.link_failures(200.0)
    assert a.events(10, 5.0) == b.events(10, 5.0)
    assert _model(seed=6).link_failures(200.0) != a.link_failures(200.0)


def test_outages_are_merged_and_ordered():
    out = _model(seed=1, domains=[
        server_domain(1, _PAIRS, mtbf=15.0, mttr=3.0)]).outages(500.0)
    assert out, "a 500 s horizon at mtbf 10 must produce outages"
    for pair, ivals in out.items():
        assert pair == (min(pair), max(pair))
        for (t0, t1), nxt in zip(ivals, ivals[1:] + [None]):
            assert 0.0 <= t0 < t1
            if nxt is not None:
                assert t1 < nxt[0], f"overlap on {pair}"


def test_domain_fails_atomically():
    dom = server_domain(1, _PAIRS, mtbf=20.0, mttr=4.0)
    assert dom.links == ((0, 1), (1, 2))
    out = FaultModel(n=4, links=(), link_mtbf=None,
                     domains=[dom], seed=2).outages(300.0)
    assert set(out) == {(0, 1), (1, 2)}
    assert out[(0, 1)] == out[(1, 2)]  # one shared outage clock


def test_flap_substreams_stable_under_domain_changes():
    plain = _model(seed=3).outages(300.0)
    with_dom = _model(seed=3, domains=[
        server_domain(0, _PAIRS, mtbf=25.0, mttr=5.0)]).outages(300.0)
    # (1, 2) and (2, 3) touch no domain: their timelines must not shift.
    assert plain[(1, 2)] == with_dom[(1, 2)]
    assert plain[(2, 3)] == with_dom[(2, 3)]


def test_link_failures_are_transient_and_sorted():
    failures = _model(seed=4).link_failures(100.0)
    assert failures
    assert all(f.repair_time is not None and f.repair_time > f.time
               for f in failures)
    assert [f.time for f in failures] == sorted(f.time for f in failures)


def test_events_alternate_per_pair():
    events = _model(seed=7, domains=[
        stride_domain(4, 1, mtbf=30.0, mttr=3.0)]).events(40, 2.5)
    assert events and {ev.kind for ev in events} <= {"fail", "repair"}
    state: dict[tuple[int, int], str] = {}
    last_iter = -1
    for ev in events:
        assert ev.iteration >= 0
        assert state.get(ev.link, "repair") != ev.kind, (
            f"double {ev.kind} on {ev.link}"
        )
        state[ev.link] = ev.kind
        assert ev.iteration >= last_iter - 39  # quantized, clamped to run
        last_iter = max(last_iter, ev.iteration)
    assert all(kind == "repair" for kind in state.values()), (
        "every storm the driver sees must heal"
    )


def test_for_topology_uses_live_pairs(vgg_plan):
    fm = FaultModel.for_topology(vgg_plan.topology, link_mtbf=5.0)
    expected = {(min(a, b), max(a, b))
                for a, b in vgg_plan.topology.graph.edges()}
    assert set(fm.links) == expected and fm.n == vgg_plan.topology.n


def test_fault_model_validation():
    with pytest.raises(ValueError):
        FaultModel(n=4, links=_PAIRS, link_mtbf=0.0)
    with pytest.raises(ValueError):
        FaultModel(n=4, links=_PAIRS, link_mtbf=1.0, link_mttr=-1.0)
    with pytest.raises(ValueError):
        server_domain(9, _PAIRS, mtbf=1.0, mttr=1.0)  # no incident links
    with pytest.raises(ValueError):
        stride_domain(4, 4, mtbf=1.0, mttr=1.0)
    with pytest.raises(ValueError):
        _model().events(10, 0.0)


# ---------------------------------------------------------------------------
# Controller: repair, validation, retry/backoff, refused admission
# ---------------------------------------------------------------------------


def _topo_pair(topo):
    a, b = next(iter(topo.graph.edges()))
    return (min(a, b), max(a, b))


def test_controller_repair_restores_incumbent(vgg_plan):
    ctrl = ReoptController(VGG16, 8, hw=HW, policy=ReoptPolicy.never(),
                           plan=vgg_plan)
    before_edges = sorted(ctrl.topology.graph.edges())
    before_links = dict(ctrl.links())
    pair = _topo_pair(ctrl.topology)
    assert ctrl.repair(pair) == 0.0  # repairing a live pair is a no-op

    ctrl.fail(pair)
    assert pair in ctrl.dead
    degraded = set(ctrl.topology.graph.edges())
    assert not degraded & {pair, (pair[1], pair[0])}
    assert pair not in ctrl.links()

    assert ctrl.repair(pair) == 0.0  # never-policy: no replan pause
    assert not ctrl.dead
    assert sorted(ctrl.topology.graph.edges()) == before_edges
    assert dict(ctrl.links()) == before_links
    a, b = pair
    assert ctrl.topology.routing.get(a, b), "direct route restored"


def test_validation_rejects_plan_on_dead_pair(vgg_plan):
    ctrl = ReoptController(
        VGG16, 8, hw=HW,
        policy=ReoptPolicy(on_failure=True, replan_latency=1e-3),
        plan=vgg_plan,
    )
    pair = _topo_pair(ctrl.topology)
    healthy = ctrl.plan  # still has edges on what is about to die
    ctrl._run_optimizer = lambda warm=True: healthy
    ctrl._estimate_plan = lambda res: 0.0  # force the would-adopt path

    pause = ctrl.fail(pair, now=0.0)
    assert pause == 0.0
    assert ctrl.n_rejected_plans == 1 and ctrl.n_replans == 0
    assert ctrl.log[-1].trigger == "failure:invalid"
    assert not ctrl.log[-1].replanned
    # Last-known-good (degraded incumbent + §7 repair) stays in force.
    assert not set(ctrl.topology.graph.edges()) & {pair, (pair[1], pair[0])}
    assert not ctrl.plan_violations(ctrl.topology)


def test_plan_violations_checks(vgg_plan):
    ctrl = ReoptController(VGG16, 8, hw=HW, policy=ReoptPolicy.never(),
                           plan=vgg_plan)
    assert ctrl.plan_violations(ctrl.topology) == []
    pair = _topo_pair(ctrl.topology)
    ctrl.dead.add(pair)
    bad = ctrl.plan_violations(vgg_plan.topology)
    assert any("dead pairs" in v for v in bad)


def test_optimizer_crash_storm_backs_off(vgg_plan):
    calls = []
    ctrl = ReoptController(
        VGG16, 8, hw=HW,
        policy=ReoptPolicy(on_failure=True, replan_latency=1e-3,
                           min_interval=0.0, replan_retries=1,
                           retry_backoff=2.0),
        plan=vgg_plan,
    )

    def boom(warm=True):
        calls.append(warm)
        raise RuntimeError("optimizer crashed")

    ctrl._run_optimizer = boom
    pairs = sorted({(min(a, b), max(a, b))
                    for a, b in ctrl.topology.graph.edges()})

    assert ctrl.fail(pairs[0], now=0.0) == 0.0
    assert len(calls) == 2  # 1 attempt + replan_retries retries
    assert ctrl.n_optimizer_errors == 2 and ctrl.n_replans == 0
    assert sum(r.trigger.endswith(":error") for r in ctrl.log) == 2

    # Storm inside the backoff window: the optimizer is NOT re-run.
    assert ctrl.fail(pairs[1], now=0.5) == 0.0
    assert len(calls) == 2
    assert ctrl.log[-1].trigger.endswith(":backoff")
    # The §7-degraded incumbent still took the cut.
    assert pairs[1] in ctrl.dead

    # Past the backoff: attempts resume, and the backoff doubles.
    assert ctrl.fail(pairs[2], now=3.0) == 0.0
    assert len(calls) == 4
    assert ctrl._backoff_until == pytest.approx(3.0 + 4.0)


def _raise_jax_runtime_error():
    import jax

    raise jax.errors.JaxRuntimeError("INTERNAL: device program failed")


def _raise_jax_trace_error():
    import jax

    jax.jit(lambda x: int(x))(1.0)  # ConcretizationTypeError


@pytest.mark.parametrize(
    "fault", [_raise_jax_runtime_error, _raise_jax_trace_error],
    ids=["runtime", "trace"],
)
def test_device_fault_propagates_from_optimizer(vgg_plan, fault):
    """A JAX compile or runtime failure is not retried with another seed
    nor logged as an ``:error`` record: it reaches the caller, so a broken
    device path cannot hide behind the last-known-good plan."""
    import jax

    calls = []
    ctrl = ReoptController(
        VGG16, 8, hw=HW,
        policy=ReoptPolicy(on_failure=True, replan_latency=1e-3,
                           min_interval=0.0, replan_retries=2),
        plan=vgg_plan,
    )

    def broken(warm=True):
        calls.append(warm)
        fault()

    ctrl._run_optimizer = broken
    with pytest.raises((jax.errors.JaxRuntimeError, jax.errors.JAXTypeError)):
        ctrl.fail(_topo_pair(ctrl.topology), now=0.0)
    assert len(calls) == 1
    assert ctrl.n_optimizer_errors == 0 and ctrl._retry_nonce == 0
    assert not any(r.trigger.endswith(":error") for r in ctrl.log)


def test_replan_deadline_discards_slow_attempts(vgg_plan):
    import time

    calls = []
    ctrl = ReoptController(
        VGG16, 8, hw=HW,
        policy=ReoptPolicy(on_failure=True, replan_latency=1e-3,
                           min_interval=0.0, replan_deadline=5e-3,
                           replan_retries=1),
        plan=vgg_plan,
    )
    healthy = ctrl.plan

    def slow(warm=True):
        calls.append(warm)
        time.sleep(0.02)  # always over the 5 ms deadline
        return healthy

    ctrl._run_optimizer = slow
    ctrl.fail(_topo_pair(ctrl.topology), now=0.0)
    # First attempt discarded for overrunning; the last permitted attempt
    # keeps its (late) result rather than returning nothing.  That result
    # then flows through normal replan processing — where validation
    # rejects it, since the stale healthy plan still routes the dead pair.
    assert len(calls) == 2
    assert ctrl.n_optimizer_errors == 1
    assert sum(r.trigger.endswith(":deadline") for r in ctrl.log) == 1
    assert ctrl.log[-1].trigger == "failure:invalid"
    assert ctrl.n_rejected_plans == 1 and ctrl.n_replans == 0


def test_place_arrival_require_hostable():
    split = {(0, 1): 1.0, (1, 0): 1.0, (2, 3): 1.0, (3, 2): 1.0}
    free = {0, 1, 2, 3}
    assert place_arrival(3, free, split, require_hostable=True) is None
    assert place_arrival(2, free, split, require_hostable=True) == (0, 1)
    # Singleton jobs have no network demand: always hostable.
    assert place_arrival(1, free, split, require_hostable=True) is not None
    # Connectivity may transit busy servers (4 is not free).
    via_busy = {(0, 4): 1.0, (4, 0): 1.0, (4, 3): 1.0, (3, 4): 1.0}
    assert place_arrival(2, {0, 3}, via_busy, require_hostable=True) == (0, 3)
    # Connected fabric: the flag is a no-op (bit-identical placement).
    ring = {}
    for i in range(4):
        ring[(i, (i + 1) % 4)] = 1.0
        ring[((i + 1) % 4, i)] = 1.0
    assert (place_arrival(3, free, ring, require_hostable=True)
            == place_arrival(3, free, ring))


def test_admit_refuses_unhostable_arrival(monkeypatch):
    jobset = JobSet(n=6, tenants=[
        TenantJob(spec=VGG16, servers=(0, 1), name="v")])
    ctrl = JobSetController(jobset, hw=HW, policy=ReoptPolicy.never())
    monkeypatch.setattr(ctrl, "links", lambda: {
        (2, 3): 1.0, (3, 2): 1.0, (4, 5): 1.0, (5, 4): 1.0})

    assert ctrl.admit(DLRM, 3, name="d", now=4.25) is None
    assert ctrl.refused == [(4.25, "d")]
    assert all(t.label != "d" for t in ctrl.jobset.tenants)

    servers, pause = ctrl.admit(DLRM, 2, name="d2", now=5.0)
    assert servers == (2, 3) and pause == 0.0
    # k > free servers is still a hard caller error, not a refusal.
    with pytest.raises(ValueError, match="only"):
        ctrl.admit(DLRM, 5, name="d3")


def test_run_online_repair_event(vgg_plan):
    pair = _topo_pair(vgg_plan.topology)
    trace = (TraceEvent(iteration=1, kind="fail", link=pair),
             TraceEvent(iteration=2, kind="repair", link=pair))
    base = run_online(VGG16, 8, hw=HW, policy=ReoptPolicy.never(),
                      n_iters=4, plan=vgg_plan)
    faulted = run_online(VGG16, 8, hw=HW, policy=ReoptPolicy.never(),
                         trace=trace, n_iters=4, plan=vgg_plan)
    assert faulted.n_failures == 1
    assert faulted.iter_times[0] == pytest.approx(base.iter_times[0],
                                                  rel=1e-9)
    # Degraded iteration can only be slower; the repaired fabric (restored
    # capacity, detours kept until the next replan) can only be faster.
    assert faulted.iter_times[1] >= base.iter_times[1] * (1 - 1e-9)
    assert faulted.iter_times[2] <= faulted.iter_times[1] * (1 + 1e-9)


# ---------------------------------------------------------------------------
# Property tests (hypothesis or the seeded shim)
# ---------------------------------------------------------------------------


def _random_storm_scenario(data):
    n = data.draw(st.integers(min_value=4, max_value=7))
    links = {}
    ring = []
    for i in range(n):
        pair = (i, (i + 1) % n)
        ring.append((min(pair), max(pair)))
        links[pair] = 100.0
        links[pair[::-1]] = 100.0
    jobs = []
    for j in range(data.draw(st.integers(min_value=1, max_value=3))):
        src = data.draw(st.integers(min_value=0, max_value=n - 1))
        dst = (src + data.draw(st.integers(min_value=1, max_value=n - 1))) % n
        nbytes = float(data.draw(st.integers(min_value=100, max_value=5000)))
        jobs.append(_flow_job(f"j{j}", nbytes=nbytes, route=(src, dst)))
    failures = []
    used = set()
    for _ in range(data.draw(st.integers(min_value=0, max_value=3))):
        pair = ring[data.draw(st.integers(min_value=0, max_value=n - 1))]
        if pair in used:
            continue  # one transient interval per pair keeps merges trivial
        used.add(pair)
        t0 = data.draw(st.floats(min_value=0.0, max_value=30.0))
        dur = data.draw(st.floats(min_value=0.1, max_value=20.0))
        failures.append(LinkFailure(time=t0, link=pair,
                                    repair_time=t0 + dur))
    failures.sort(key=lambda f: (f.time, f.link))
    restart = {jobs[0].name: data.draw(st.floats(min_value=0.0,
                                                 max_value=3.0))}
    return Scenario(links=links, jobs=jobs, n=n,
                    failures=tuple(failures), restart_s=restart)


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_random_transient_storms_conserve_bytes(data):
    """Every fault is transient, so every byte is eventually delivered —
    exactly the fault-free run's delivery."""
    sc = _random_storm_scenario(data)
    calm = Scenario(links=dict(sc.links), jobs=sc.jobs, n=sc.n)
    r_storm = SimEngine().run(sc)
    r_calm = SimEngine().run(calm)
    assert not r_storm.stalled
    assert r_storm.delivered == r_calm.delivered
    assert np.isfinite(r_storm.makespan)
    for job in r_storm.downtime:
        assert 0.0 <= r_storm.availability(job) <= 1.0


@settings(max_examples=12, deadline=None)
@given(st.data())
def test_heap_dense_identical_through_fail_repair(data):
    """The heap and dense max-min fills stay bit-identical through
    fail -> repair round trips (capacity snapshots restore exactly)."""
    sc = _random_storm_scenario(data)
    results = {}
    for method in ("heap", "dense"):
        os.environ["REPRO_MAXMIN_METHOD"] = method
        try:
            results[method] = SimEngine().run(Scenario(
                links=dict(sc.links), jobs=sc.jobs, n=sc.n,
                failures=sc.failures, restart_s=dict(sc.restart_s)))
        finally:
            os.environ.pop("REPRO_MAXMIN_METHOD", None)
    h, d = results["heap"], results["dense"]
    assert h.makespan == d.makespan  # bit-identical, no tolerance
    assert h.job_finish == d.job_finish
    assert h.delivered == d.delivered
    assert h.downtime == d.downtime and h.restarts == d.restarts


_PROP_PLAN = None


def _prop_plan():
    global _PROP_PLAN
    if _PROP_PLAN is None:
        _PROP_PLAN = alternating_optimize(VGG16, 8, HW, rounds=1,
                                          mcmc_iters=10, seed=0)
    return _PROP_PLAN


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_fail_repair_interleavings_keep_degree_budget(data):
    """Any interleaving of fails and repairs keeps the incumbent inside
    the degree budget with no dead-pair edges; repairing everything
    restores the original edge multiset bit for bit."""
    plan = _prop_plan()
    ctrl = ReoptController(VGG16, 8, hw=HW, policy=ReoptPolicy.never(),
                           plan=plan)
    original = sorted(ctrl.topology.graph.edges())
    budget = ctrl.topology.degree + 1
    pairs = sorted({(min(a, b), max(a, b))
                    for a, b in ctrl.topology.graph.edges()})
    for _ in range(data.draw(st.integers(min_value=1, max_value=8))):
        pair = pairs[data.draw(st.integers(min_value=0,
                                           max_value=len(pairs) - 1))]
        if data.draw(st.integers(min_value=0, max_value=1)) and ctrl.dead:
            pair = sorted(ctrl.dead)[0]
            ctrl.repair(pair)
        else:
            ctrl.fail(pair)
        g = ctrl.topology.graph
        degs = [d for _, d in g.out_degree()]
        assert max(degs, default=0) <= budget
        for dead in ctrl.dead:
            assert not g.has_edge(*dead) and not g.has_edge(dead[1], dead[0])
            assert dead not in ctrl.links()
    for pair in sorted(ctrl.dead):
        ctrl.repair(pair)
    assert sorted(ctrl.topology.graph.edges()) == original
