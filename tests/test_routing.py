import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.routing import (
    Route,
    RoutingTable,
    allreduce_routes,
    bandwidth_tax,
    coin_change_mod,
    path_length_stats,
)


def test_coin_change_reaches_every_distance():
    bt = coin_change_mod(16, [1, 3, 7])
    assert set(bt) == set(range(1, 16))
    for m, coins in bt.items():
        assert sum(coins) % 16 == m


def test_coin_change_minimality_stride1():
    bt = coin_change_mod(8, [1])
    for m, coins in bt.items():
        assert len(coins) == m  # only +1 hops available


def test_coin_change_uses_big_stride():
    bt = coin_change_mod(16, [1, 5])
    # distance 10 = 5+5 (2 hops), not 10 x 1.
    assert len(bt[10]) == 2


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(min_value=2, max_value=64),
    data=st.data(),
)
def test_coin_change_complete_for_coprime_strides(n, data):
    import math

    cands = [p for p in range(1, n) if math.gcd(p, n) == 1]
    strides = data.draw(
        st.lists(st.sampled_from(cands), min_size=1, max_size=3, unique=True)
    )
    bt = coin_change_mod(n, strides)
    assert set(bt) == set(range(1, n))


def test_allreduce_routes_follow_rings():
    members = (0, 1, 2, 3, 4, 5, 6, 7)
    table = allreduce_routes(members, [1, 3])
    # every ordered pair routed
    assert len(table.routes) == 8 * 7
    for (src, dst), routes in table.routes.items():
        for r in routes:
            assert r.path[0] == src and r.path[-1] == dst
            for a, b in zip(r.path[:-1], r.path[1:]):
                assert (b - a) % 8 in (1, 3)  # every hop rides a ring edge


def _eager_rings(groups):
    """Reference: every group's ring routes spelled out in order, a later
    group's route replacing an earlier one on a shared pair."""
    routes = {}
    for members, strides in groups:
        n = len(members)
        bt = coin_change_mod(n, strides)
        for i in range(n):
            for m, coins in bt.items():
                path = [i]
                for c in coins:
                    path.append((path[-1] + c) % n)
                routes[(members[i], members[(i + m) % n])] = [
                    tuple(members[v] for v in path)
                ]
    return routes


def _paths(rs):
    return [r.path for r in rs]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lazy_ring_table_matches_eager_build(seed):
    """Ring routes built on demand agree pair by pair, and in spelled-out
    order, with the eager build; routes set or added on top win."""
    import networkx as nx

    rng = np.random.default_rng(seed)
    n = 24
    groups = [
        (tuple(int(v) for v in rng.choice(n, size=k, replace=False)),
         [1, 3] if k > 4 else [1])
        for k in (12, 8, 5)
    ]
    ref = _eager_rings(groups)
    mp_pair, extra = (0, 1), (2, 3)

    def build(check=False):
        table = RoutingTable()
        for members, strides in groups:
            table.add_rings(members, strides)
        if check:
            for s in range(n):
                for t in range(n):
                    assert _paths(table.get(s, t)) == ref.get((s, t), [])
        table.set(*mp_pair, [Route(path=(0, 2, 1))])
        table.add(*extra, (2, 5, 3))
        return table

    table = build(check=True)
    ref[mp_pair] = [(0, 2, 1)]
    ref[extra] = ref.get(extra, []) + [(2, 5, 3)]
    assert [(p, _paths(rs)) for p, rs in table.routes.items()] == list(
        ref.items()
    )

    # Dead links: routes that avoid them stay, the rest take a shortest
    # path on what is left, and unreachable pairs drop out.
    g = nx.MultiDiGraph()
    g.add_nodes_from(range(n))
    for p in ref.values():
        for path in p:
            g.add_edges_from(zip(path[:-1], path[1:]))
    edges = sorted(set(g.edges()))
    removed = {edges[i] for i in rng.choice(len(edges), size=6, replace=False)}
    for e in removed:
        while g.has_edge(*e):
            g.remove_edge(*e)
    simple = nx.DiGraph(g)
    want = {}
    for pair, paths in ref.items():
        keep = [q for q in paths
                if not any(h in removed for h in zip(q[:-1], q[1:]))]
        if not keep:
            try:
                keep = [tuple(nx.shortest_path(simple, *pair))]
            except nx.NetworkXNoPath:
                continue
        want[pair] = keep
    after = build().rerouted(removed, g)
    for pair in ref:
        assert _paths(after.get(*pair)) == want.get(pair, [])
    over = after.overlay()
    over.add(4, 9, (4, 9))
    assert _paths(over.get(4, 9)) == want.get((4, 9), []) + [(4, 9)]
    assert _paths(after.get(4, 9)) == want.get((4, 9), [])
    spelled = table.rerouted(removed, g).routes
    assert [(p, _paths(rs)) for p, rs in spelled.items()] == list(want.items())


def test_bandwidth_tax_direct_is_one():
    t = RoutingTable()
    t.add(0, 1, (0, 1))
    assert bandwidth_tax([(0, 1, 100.0)], t) == pytest.approx(1.0)


def test_bandwidth_tax_two_hops():
    t = RoutingTable()
    t.add(0, 2, (0, 1, 2))
    assert bandwidth_tax([(0, 2, 100.0)], t) == pytest.approx(2.0)


def test_path_length_stats():
    t = RoutingTable()
    t.add(0, 1, (0, 1))
    t.add(0, 2, (0, 1, 2))
    t.add(0, 3, (0, 1, 2, 3))
    stats = path_length_stats(t)
    assert stats["mean"] == pytest.approx(2.0)
    assert stats["max"] == 3
