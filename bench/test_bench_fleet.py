"""The traffic generator deals the same work out in a seeded order."""
from __future__ import annotations

from bench.fleet import HOST, Fleet, alto_costs
from bench.harness import Cell


def _fleet(root, cell, seed):
    c = Cell(cell, root=root)
    return Fleet(c.config, c.traffic, seed)


def _islands(f):
    return {n: f.topology.island_of(n) for n in f.names}


def test_same_seed_same_fleet_and_schedule(tiny_root):
    for cell in ("flat-n2000.crowd", "p4p-n2000-i8.crowd"):
        a, b = _fleet(tiny_root, cell, 11), _fleet(tiny_root, cell, 11)
        assert a.speed == b.speed
        if a.topology is not None:
            assert _islands(a) == _islands(b)
            assert a.cost == b.cost


def test_other_seed_same_work_in_another_order(tiny_root):
    a = _fleet(tiny_root, "flat-n2000.crowd", 11)
    b = _fleet(tiny_root, "flat-n2000.crowd", 2 ** 31 + 12)
    fleet_speeds = lambda f: [f.speed[n] for n in f.names]
    assert fleet_speeds(a) != fleet_speeds(b)
    assert sorted(fleet_speeds(a)) == sorted(fleet_speeds(b))
    pa = _fleet(tiny_root, "p4p-n2000-i8.crowd", 11)
    pb = _fleet(tiny_root, "p4p-n2000-i8.crowd", 12)
    assert pa.topology.latency_s == pb.topology.latency_s
    # the island seats are the deployment's, not the seed's
    assert _islands(pa) == _islands(pb)
    counts = [list(_islands(pa).values()).count(k) for k in range(8)]
    assert max(counts) - min(counts) <= 1


def test_crowd_fetches_from_the_origin_alone(tiny_root):
    f = _fleet(tiny_root, "flat-n2000.crowd", 5)
    live = f.live()
    assert [a.node_id for a in live] == sorted([HOST] + f.names)
    assert f.n_pieces == f.manifest.n_pieces == 16
    assert f.verified_pieces() == 0     # nothing fetched yet
    outcomes = f.outcomes()
    assert outcomes == {"delivered": 0, "corrupt": 0, "overdue": 0}


def test_alto_costs_grow_with_latency_and_cap():
    lat = [[0.0, 0.0049, 0.0204, 1.0],
           [0.0049, 0.0, 0.08, 0.03],
           [0.0204, 0.08, 0.0, 0.0],
           [1.0, 0.03, 0.0, 0.0]]
    cost = alto_costs(lat)
    assert [cost[i][i] for i in range(4)] == [0, 0, 0, 0]
    assert cost[0][1] == 1 and cost[0][2] == 3 and cost[1][2] == 9
    assert cost[0][3] == 15             # capped
    assert cost[2][3] == 1              # never cheaper than 1 across
