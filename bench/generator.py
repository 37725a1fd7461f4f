"""Traffic generator: CircuitNet-like partitions at Table-1 scale.

The benchmark's own copy of the partition generator (the program keeps its
own in ``repro/graphs/generator.py``), so that no change to the program can
move the yardstick.  It reproduces the structural statistics the paper
depends on (Table 1, Fig. 4): two node types, heavy-tailed ``near``
(cell->cell) degrees with a bulk around 30-60 and rows up to 260, ``pin``
(cell->net) fan-outs of 2-6, ``pinned`` = ``pin`` transposed, and a
congestion label that follows local wiring density.

A traffic mix is a data file, ``bench/traffic/<name>.json``: the list of
partition sizes in its pool, the seed of their structure, and how the
driver feeds them.  Every run seed gets the same graphs, so the same work
and the same compiled shapes; the run seed draws the feature and label
noise, the weights and the order in which the pool is visited.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# Table 1 anchor statistics (per-partition node counts for the three designs).
TABLE1 = {
    "small": dict(n_net=(3269, 4628), n_cell=(7347, 7767), graphs=2),
    "medium": dict(n_net=(5331, 7271), n_cell=(9493, 9733), graphs=3),
    "large": dict(n_net=(5883, 9100), n_cell=(9341, 9816), graphs=4),
}

def _powerlaw_degrees(rng, n, bulk=40, tail_max=260, alpha=1.8):
    """Heavy-tailed degrees: lognormal bulk + pareto evil-row tail (Fig. 4)."""
    bulk_deg = rng.lognormal(mean=np.log(bulk), sigma=0.6, size=n)
    evil = rng.random(n) < 0.02
    tail = (rng.pareto(alpha, size=n) + 1.0) * bulk * 2.0
    deg = np.where(evil, tail, bulk_deg)
    return np.clip(deg, 1, tail_max).astype(np.int64)


def generate_partition(rng: np.random.Generator, n_cell: int, n_net: int,
                       feat_cell: int = 16, feat_net: int = 16,
                       near_bulk: int = 40,
                       noise: np.random.Generator = None) -> dict:
    """One partition: COO edges {etype: (dst, src)}, features and label.

    ``rng`` draws the structure (placement, edges); ``noise``, where given,
    draws the feature and label noise, so that one structure can carry
    different data."""
    noise = rng if noise is None else noise
    pos = rng.random((n_cell, 2)).astype(np.float32)
    deg = _powerlaw_degrees(rng, n_cell, bulk=near_bulk)
    # spatial neighbours from a window of cells sorted by x: cheap, and it
    # keeps the degree law, which is what the kernels see
    dst_l, src_l = [], []
    order = np.argsort(pos[:, 0], kind="stable")
    rank_of = np.empty(n_cell, np.int64)
    rank_of[order] = np.arange(n_cell)
    for i in range(n_cell):
        d = int(deg[i])
        lo = max(rank_of[i] - 4 * d, 0)
        hi = min(rank_of[i] + 4 * d + 1, n_cell)
        cand = order[lo:hi]
        cand = cand[cand != i]
        if cand.size == 0:
            continue
        take = min(d, cand.size)
        nbrs = rng.choice(cand, size=take, replace=False)
        dst_l.append(np.full(take, i)), src_l.append(nbrs)
    near_dst = np.concatenate(dst_l)
    near_src = np.concatenate(src_l)

    fanout = rng.integers(2, 7, size=n_net)
    pin_net = np.repeat(np.arange(n_net), fanout)
    pin_cell = rng.integers(0, n_cell, size=pin_net.size)
    key = pin_cell.astype(np.int64) * n_net + pin_net
    _, uniq = np.unique(key, return_index=True)
    pin_cell, pin_net = pin_cell[uniq], pin_net[uniq]

    coo = {"near": (near_dst, near_src), "pin": (pin_net, pin_cell),
           "pinned": (pin_cell, pin_net)}

    near_deg = np.bincount(near_dst, minlength=n_cell).astype(np.float32)
    pin_deg = np.bincount(pin_cell, minlength=n_cell).astype(np.float32)
    x_cell = np.stack([pos[:, 0], pos[:, 1],
                       near_deg / near_deg.max(),
                       pin_deg / max(pin_deg.max(), 1.0)], 1)
    x_cell = np.concatenate(
        [x_cell, noise.normal(0, 0.1, (n_cell, feat_cell - 4))], 1
    ).astype(np.float32)
    net_fan = np.bincount(pin_net, minlength=n_net).astype(np.float32)
    x_net = np.concatenate(
        [net_fan[:, None] / max(net_fan.max(), 1.0),
         noise.normal(0, 0.1, (n_net, feat_net - 1))], 1).astype(np.float32)

    dens = near_deg + 2.0 * pin_deg
    dens = (dens - dens.mean()) / (dens.std() + 1e-6)
    y = (dens + noise.normal(0, 0.25, n_cell)).astype(np.float32)
    y = (1.0 / (1.0 + np.exp(-y))).astype(np.float32)
    return dict(coo=coo, x_cell=x_cell, x_net=x_net, y=y,
                n_cell=n_cell, n_net=n_net)


def load_traffic(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def pool_sizes(traffic: dict) -> List[Dict[str, int]]:
    """(n_cell, n_net) of every pool entry, scaled by ``traffic['scale']``
    (1.0 in every cell; the CPU tests shrink it)."""
    scale = float(traffic.get("scale", 1.0))
    return [dict(n_cell=max(int(p["n_cell"] * scale), 16),
                 n_net=max(int(p["n_net"] * scale), 8))
            for p in traffic["pool"]]


def make_pool(traffic: dict, seed: int) -> List[dict]:
    """Every partition of the pool: structure from the traffic's
    ``structure_seed``, noise from ``seed``.  Each entry gets its own child
    streams, so an entry does not depend on the ones before it."""
    n = len(traffic["pool"])
    shapes = np.random.SeedSequence(traffic["structure_seed"]).spawn(n)
    noises = np.random.SeedSequence([seed, 0]).spawn(n)
    out = []
    for ss, ns, size in zip(shapes, noises, pool_sizes(traffic)):
        part = generate_partition(
            np.random.default_rng(ss), size["n_cell"], size["n_net"],
            traffic.get("feat_cell", 16), traffic.get("feat_net", 16),
            traffic.get("near_bulk", 40), noise=np.random.default_rng(ns))
        out.append(part)
    return out


def visit_order(n: int, seed: int) -> List[int]:
    """The seeded order in which the pool is cycled."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    return [int(i) for i in rng.permutation(n)]


def weight_seed(seed: int) -> int:
    """A 31-bit seed for the model's initial weights, drawn from ``seed``
    (run seeds may exceed what a PRNG key takes)."""
    return int(np.random.SeedSequence([seed, 2]).generate_state(1)[0]
               & 0x7FFFFFFF)
