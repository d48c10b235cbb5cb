"""Admission decisions on a shared cluster, through the planner's online
controller (``repro.core.online.JobSetController.admit``).

Closed loop: each decision admits one arriving tenant through the fused
candidate x tempering-ladder grid, then the oldest tenant departs with no
replan.  The arrival is of the departing tenant's type, so the residents
keep the mix's apportionment (``bench.gen.resident_order``) and every
decision, in every seed, prices the same set of tenants plus one arrival
whose type cycles through the residents' order; the seed draws that order
and the search's own seed.

The check prices every plan the search returned in the window with the
plain reference (``bench/ref/pricing.py``): the grid kernel's energy of
the winning state and the plan's reported iteration time must both agree
with the reference price, and every adopted plan must keep the
configuration's guarantees.  The control prices the same states in the precision below
the one the decision needs.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from bench import gen
from bench.core import Compared, Window
from bench.ref import pricing

# Limits on the relative gap between a price the program reports and the
# reference's float64 price; PERF.md gives the readings they were set from.
ENERGY_GAP_LIMIT = 1e-5
PRICE_GAP_LIMIT = 1e-5
# Readings of ``calibration()`` that have to come out correct: the program,
# and float32 pricing, the precision the decision needs.  The control fails.
MUST_PASS = ("program", "float32")


@dataclass
class Decision:
    label: str
    kind: str
    adopted: bool
    results: list = field(default_factory=list)  # every plan the search returned
    plan: object = None
    jobset: object = None
    errors: list = field(default_factory=list)


def program_inputs(config: dict):
    """The program's own types, built from the configuration's numbers."""
    from repro.core.netsim import HardwareSpec
    from repro.core.online import ReoptPolicy
    from repro.core.workloads import JobSpec

    hwc = config["hardware"]
    hw = HardwareSpec(link_bandwidth=hwc["link_bandwidth"],
                      degree=config["degree"],
                      compute_flops=hwc["compute_flops"],
                      compute_efficiency=hwc["compute_efficiency"],
                      link_latency=hwc["link_latency"])
    pol = dict(config["policy"])
    pol["temperatures"] = tuple(pol["temperatures"])
    policy = ReoptPolicy(**pol)
    jobs = {}
    for name, j in config["jobs"].items():
        jobs[name] = JobSpec(
            name=name, batch_per_gpu=int(j["batch_per_gpu"]),
            dense_params=float(j["dense_params"]),
            flops_per_sample=float(j["flops_per_sample"]),
            n_tables=int(j.get("n_tables", 0)),
            table_rows=float(j.get("table_rows", 0.0)),
            table_dim=int(j.get("table_dim", 0)),
            bytes_per_param=int(j["bytes_per_param"]),
            bytes_per_activation=int(j["bytes_per_activation"]),
        )
    return hw, policy, jobs


def tenant_views(config: dict, jobset, strategies) -> list:
    return [
        pricing.TenantView(
            label=t.label, job=config["jobs"][t.spec.name],
            servers=tuple(int(s) for s in t.servers), weight=float(t.weight),
            mode=strategies[t.label].mode,
            table_hosts=tuple(int(h) for h in strategies[t.label].table_hosts),
            schedule=strategies[t.label].schedule,
        )
        for t in jobset.tenants
    ]


def plan_parts(plan):
    """The fabric as the plan decides it: links, rings and routes."""
    topo = plan.topology
    links = [(int(a), int(b)) for a, b in topo.graph.edges()]
    rings = {tuple(int(m) for m in members): [
        [int(r.members[(i * r.p) % len(r.members)]) for i in range(len(r.members))]
        for r in rs] for members, rs in topo.rings.items()}

    def routes(s, d):
        return [tuple(int(v) for v in r.path) for r in topo.routing.get(s, d)]

    return links, rings, routes


def price(config: dict, plan, jobset, dtype=np.float64) -> float:
    links, rings, routes = plan_parts(plan)
    return pricing.plan_price(
        tenant_views(config, jobset, plan.strategies), links, rings, routes,
        config["hardware"], dtype=dtype)


class Runner:
    def __init__(self, cell, devices, seed: int, spans, log):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.spans = spans
        self.log = log
        self.decisions: list[Decision] = []

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        from repro.core.online import JobSetController
        from repro.core.workloads import JobSet, TenantJob

        cfg = self.config
        self.hw, self.policy, self.jobs = program_inputs(cfg)
        k, resident = cfg["job_size"], cfg["resident"]
        order = gen.resident_order([tuple(m) for m in cfg["mix"]], resident,
                                   self.seed)
        tenants = [
            TenantJob(spec=self.jobs[name], name=f"r{i}",
                      servers=tuple(range(i * k, (i + 1) * k)))
            for i, name in enumerate(order)
        ]
        self.ctl = JobSetController(
            JobSet(n=cfg["servers"], tenants=tenants), hw=self.hw,
            policy=self.policy, seed=gen.small_seed(self.seed),
        )
        probe = self.ctl.estimated_iter_time

        def timed_probe(*a, **kw):
            with self.spans.span("probe"):
                return probe(*a, **kw)

        self.ctl.estimated_iter_time = timed_probe
        optimize = self.ctl._guarded_optimize
        self._results: list = []

        def recorded_optimize(*a, **kw):
            res = optimize(*a, **kw)
            if res is not None:
                self._results.append(res)
            return res

        self.ctl._guarded_optimize = recorded_optimize
        t0 = time.perf_counter()
        self.ctl.ensure_plan()
        self.log(f"initial plan {time.perf_counter() - t0:.3f} s")
        self._n = 0
        for _ in range(self.traffic["warmup_decisions"]):
            t0 = time.perf_counter()
            d = self._decide()
            self.log(f"warm-up decision {time.perf_counter() - t0:.3f} s, "
                     f"adopted {d.adopted}")

    def _decide(self) -> Decision:
        ctl = self.ctl
        name = ctl.jobset.tenants[0].spec.name
        label = f"a{self._n}"
        now = float(self._n)
        self._n += 1
        before = ctl._plan
        n_log, n_res = len(ctl.log), len(self._results)
        with self.spans.span("admit"):
            placed = ctl.admit(self.jobs[name], self.config["job_size"],
                               name=label, now=now)
        adopted = ctl._plan is not before
        d = Decision(label=label, kind=name, adopted=adopted,
                     results=self._results[n_res:])
        if placed is None:
            d.errors.append("refused")
        d.errors += [r.trigger for r in ctl.log[n_log:]
                     if r.trigger.endswith((":error", ":deadline", ":invalid"))]
        if adopted:
            d.plan, d.jobset = ctl._plan, ctl.jobset
        with self.spans.span("depart"):
            ctl.depart(ctl.jobset.tenants[0].label, now=now)
        return d

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float, tracer) -> Window:
        compiles = []
        import jax.monitoring as mon

        from jax._src.dispatch import BACKEND_COMPILE_EVENT

        def on_compile(event, duration, **kw):
            if event == BACKEND_COMPILE_EVENT:
                compiles.append(duration)

        mon.register_event_duration_secs_listener(on_compile)
        tracer.start()
        t0 = time.perf_counter()
        try:
            while True:
                t1 = time.perf_counter()
                with self.spans.span("decision"):
                    d = self._decide()
                self.decisions.append(d)
                self.log(f"decision {d.label} ({d.kind}) "
                         f"{time.perf_counter() - t1:.3f} s, adopted {d.adopted}")
                if time.perf_counter() - t0 >= seconds:
                    break
        finally:
            elapsed = time.perf_counter() - t0
            tracer.stop()
            mon.unregister_event_duration_listener(on_compile)
        n = len(self.decisions)
        self.spans.counters["compiles"] = len(compiles)
        self.spans.counters["compile_s"] = float(sum(compiles))
        self.log(f"decisions {n}, adopted "
                 f"{sum(d.adopted for d in self.decisions)}, compiles "
                 f"{len(compiles)} ({sum(compiles):.3f} s)")
        return Window(elapsed=elapsed, units=n, unit_name="decisions",
                      e2e={"decision_s": elapsed / n}, traced_units=n)

    def release(self) -> None:
        self.ctl = None

    # -- check ----------------------------------------------------------------

    def readings(self, dtype=np.float64) -> dict:
        """The gaps of every plan the search returned in the window against
        the reference price, and the adopted plans' breaches of the
        configuration's guarantees.  With ``dtype`` other than float64 the
        reference in that precision takes the program's place."""
        cfg = self.config
        energy, reported, bad = [], [], []
        for d in self.decisions:
            for plan in d.results:
                ref = price(cfg, plan, plan.jobset)
                low = (price(cfg, plan, plan.jobset, dtype)
                       if dtype is not np.float64 else None)
                energy.append(pricing.rel_gap(
                    min(plan.rounds) if low is None else low, ref))
                reported.append(pricing.rel_gap(
                    plan.iter_time if low is None else low, ref))
            if d.adopted:
                links, _, _ = plan_parts(d.plan)
                bad += pricing.plan_violations(
                    tenant_views(cfg, d.jobset, d.plan.strategies), links,
                    cfg["degree"], cfg["servers"])
        return {"energy_gap": max(energy, default=float("nan")),
                "price_gap": max(reported, default=float("nan")),
                "violations": bad, "priced": len(energy),
                "adopted": sum(d.adopted for d in self.decisions)}

    def calibration(self) -> dict:
        """Readings of the program and of the control (the reference in
        the precision below the one the decision needs) on the same plans."""
        import ml_dtypes

        strip = lambda r: {k: (len(v) if k == "violations" else v)  # noqa: E731
                           for k, v in r.items()}
        return {"program": strip(self.readings()),
                "control": strip(self.readings(ml_dtypes.bfloat16)),
                "float32": strip(self.readings(np.float32))}

    def compare(self, r: dict) -> list[Compared]:
        bad = r["violations"]
        return [
            Compared("energy_gap", r["energy_gap"], ENERGY_GAP_LIMIT),
            Compared("price_gap", r["price_gap"], PRICE_GAP_LIMIT),
            Compared("violations", float(bad if isinstance(bad, int) else len(bad)), 0.0),
            Compared("priced", float(r["priced"]), float(len(self.decisions)),
                     at_least=True),
        ]

    def check(self):
        r = self.readings()
        for v in r["violations"][:8]:
            self.log(f"violation: {v}")
        failed = sum(bool(d.errors) for d in self.decisions)
        for d in self.decisions:
            if d.errors:
                self.log(f"decision {d.label}: {d.errors}")
        return self.compare(r), len(self.decisions), failed
