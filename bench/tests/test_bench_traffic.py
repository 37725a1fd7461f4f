"""Traffic is a function of the seed: same seed, same partitions and order;
every seed gets the same graphs (sizes and edges) with its own data."""

import numpy as np
import pytest

import generator as G

SMALL = dict(G.load_traffic("table1_stream"), scale=0.02)


def _same(a, b):
    for et in ("near", "pin", "pinned"):
        for x, y in zip(a["coo"][et], b["coo"][et]):
            if not np.array_equal(x, y):
                return False
    return all(np.array_equal(a[k], b[k]) for k in ("x_cell", "x_net", "y"))


@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_same_seed_same_traffic(seed):
    a, b = G.make_pool(SMALL, seed), G.make_pool(SMALL, seed)
    assert all(_same(x, y) for x, y in zip(a, b))
    assert G.visit_order(len(a), seed) == G.visit_order(len(b), seed)
    assert G.weight_seed(seed) == G.weight_seed(seed) < 2**31


def test_seeds_share_graphs_not_data():
    a, b = G.make_pool(SMALL, 1), G.make_pool(SMALL, 2)
    for x, y in zip(a, b):
        assert (x["n_cell"], x["n_net"]) == (y["n_cell"], y["n_net"])
        for et in ("near", "pin", "pinned"):
            assert all(np.array_equal(u, v)
                       for u, v in zip(x["coo"][et], y["coo"][et]))
        assert not np.array_equal(x["y"], y["y"])
        assert not np.array_equal(x["x_net"], y["x_net"])
    assert G.visit_order(9, 1) != G.visit_order(9, 2)
    assert sorted(G.visit_order(9, 1)) == list(range(9))


def test_full_scale_sizes_follow_table1():
    for name in ("large_resident", "table1_stream"):
        t = G.load_traffic(name)
        for p, s in zip(t["pool"], G.pool_sizes(t)):
            spec = G.TABLE1[p["design"]]
            assert spec["n_cell"][0] <= s["n_cell"] <= spec["n_cell"][1]
            assert spec["n_net"][0] <= s["n_net"] <= spec["n_net"][1]
