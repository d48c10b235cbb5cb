"""Plain reference of a shared-cluster plan's price and validity.

It restates the TopoOpt fluid model (arXiv:2202.00433, section 5) from the
job numbers in the configuration file, and reads from the plan only what
the plan decides: each tenant's servers and strategy, the shared fabric's
links, the AllReduce rings and the routes of model-parallel traffic.

* A tenant's traffic: data parallelism all-reduces every parameter
  (embedding tables included) over the tenant's servers; a hybrid DLRM
  strategy keeps the dense part in the all-reduce and serves its tables
  from ``table_hosts``, each host sending every other server its share of
  looked-up rows and receiving their gradients.
* Link loads: a ring all-reduce of ``M`` bytes over ``k`` servers puts
  ``2 (k-1)/k M`` on every edge of its ring, split evenly over the group's
  rings; a model-parallel flow puts its bytes on every hop of its route,
  split evenly over the pair's routes, with a shortest path where the plan
  routes no such pair.  Parallel links between a pair share its load.
* Times: communication is the most loaded link's bytes over its capacity;
  a tenant's iteration is its compute time plus the union's communication
  time; the plan's price is the weight-weighted mean over tenants.

``dtype`` sets the precision every sum and quotient is rounded to, so the
same function prices in float64 (the reference) and in bfloat16 (the
control).
"""

from __future__ import annotations

from dataclasses import dataclass

import networkx as nx
import numpy as np


@dataclass(frozen=True)
class TenantView:
    label: str
    job: dict  # the configuration's numbers for this job type
    servers: tuple[int, ...]
    weight: float
    mode: str
    table_hosts: tuple[int, ...]
    schedule: str


def _round(dtype):
    if dtype is np.float64:
        return float
    return lambda x: float(dtype(x))


def tenant_traffic(t: TenantView):
    """(all-reduce groups [(members, bytes)], mp {(src, dst): bytes}) in
    cluster server ids."""
    job = t.job
    k = len(t.servers)
    bpp = job["bytes_per_param"]
    dense = job["dense_params"] * bpp
    mp: dict = {}
    if t.mode == "hybrid" and job.get("n_tables") and t.table_hosts:
        hosts = sorted(set(t.table_hosts))
        act = (job["batch_per_gpu"] * job["table_dim"]
               * job["bytes_per_activation"] * job["n_tables"] / len(hosts))
        for h in hosts:
            for j in range(k):
                if j == h:
                    continue
                for pair in ((h, j), (j, h)):
                    key = (t.servers[pair[0]], t.servers[pair[1]])
                    mp[key] = mp.get(key, 0.0) + act
        groups = [(t.servers, dense)]
    else:
        params = job["dense_params"] + job.get("n_tables", 0) * job.get(
            "table_rows", 0) * job.get("table_dim", 0)
        groups = [(t.servers, params * bpp)]
    return groups, mp


def plan_price(tenants: list[TenantView], graph, rings: dict, routes,
               hw: dict, dtype=np.float64) -> float:
    """The plan's weighted mean iteration time.

    ``graph``: the fabric's directed links, parallel links repeated, as
    (src, dst) pairs.  ``rings``: members tuple -> list of ring node orders.
    ``routes(src, dst)``: the plan's paths for a model-parallel pair (a list
    of node tuples, empty where the plan has none)."""
    r = _round(dtype)
    loads: dict = {}
    n_par: dict = {}
    for a, b in graph:
        n_par[(a, b)] = n_par.get((a, b), 0) + 1
    simple = None
    for t in tenants:
        if t.schedule != "ring":
            raise ValueError(f"reference prices ring schedules only, not {t.schedule!r}")
        groups, mp = tenant_traffic(t)
        for members, nbytes in groups:
            k = len(members)
            orders = rings.get(tuple(members), [])
            if k <= 1 or not orders or nbytes == 0.0:
                continue
            share = r(r(2.0 * (k - 1) / k * nbytes) / len(orders))
            for order in orders:
                for i in range(len(order)):
                    e = (order[i], order[(i + 1) % len(order)])
                    loads[e] = r(loads.get(e, 0.0) + share)
        for (s, d), nbytes in sorted(mp.items()):
            paths = routes(s, d)
            if not paths:
                if simple is None:
                    simple = nx.DiGraph()
                    simple.add_edges_from(graph)
                try:
                    paths = [tuple(nx.shortest_path(simple, s, d))]
                except (nx.NetworkXNoPath, nx.NodeNotFound):
                    continue
            share = r(nbytes / len(paths))
            for p in paths:
                for e in zip(p[:-1], p[1:]):
                    loads[e] = r(loads.get(e, 0.0) + share)
    comm = 0.0
    for e, nbytes in loads.items():
        cap = r(max(1, n_par.get(e, 1)) * hw["link_bandwidth"])
        comm = max(comm, r(nbytes / cap))
    total = 0.0
    wsum = 0.0
    for t in tenants:
        k = len(t.servers)
        flops = t.job["flops_per_sample"] * t.job["batch_per_gpu"] * k
        comp = r(flops / (k * hw["compute_flops"] * hw["compute_efficiency"]))
        total = r(total + r(t.weight * r(comp + comm)))
        wsum += t.weight
    return r(total / wsum)


def plan_violations(tenants: list[TenantView], graph, degree: int,
                    n: int) -> list[str]:
    """Breaches of what the configuration guarantees: no server drives or
    takes more than ``degree`` links, every link joins two of the cluster's
    servers, and every tenant's servers reach one another over the fabric."""
    out = []
    outdeg: dict = {}
    indeg: dict = {}
    for a, b in graph:
        if not (0 <= a < n and 0 <= b < n) or a == b:
            out.append(f"bad link {(a, b)}")
        outdeg[a] = outdeg.get(a, 0) + 1
        indeg[b] = indeg.get(b, 0) + 1
    over = [v for v, d in {**outdeg}.items() if d > degree]
    over += [v for v, d in indeg.items() if d > degree]
    if over:
        out.append(f"{len(over)} servers over degree {degree}")
    g = nx.DiGraph()
    g.add_edges_from(graph)
    comp = {}
    for ci, nodes in enumerate(nx.strongly_connected_components(g)):
        for v in nodes:
            comp[v] = ci
    for t in tenants:
        if len(t.servers) > 1 and len({comp.get(v, -1 - v) for v in t.servers}) > 1:
            out.append(f"tenant {t.label} split across the fabric")
    return out


def rel_gap(value: float, ref: float) -> float:
    return abs(value - ref) / abs(ref)
