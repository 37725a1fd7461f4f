"""Readings that set the upper end of a training cell's limits.

    python3 bench/control.py --workload drcgnn-large-resident --seeds 11 12 13

For each seed it runs the plain reference's first three steps three more
times at the cell's own size and compares them with the float32 reference
by the same three numbers the cell compares (reference/compare.py):

* ``control``: the reference in the program's place, computed in
  bfloat16, the nearest precision below the float32 the configuration
  states;
* ``half_batch``: the reference with the loss taken over half of the cells
  only, a planted fault;
* ``unchanged``: the reference with a step that returns the state it was
  given (it reads 1 on ``grad_gap`` and ``change_gap`` by construction;
  the run gives its ``loss_gap``).

The benchmark's own runs never run this.  It needs the accelerator; the
CPU tests call ``readings_for`` at a small size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings_for(workload: str, seed: int, overrides: dict = None) -> dict:
    import cell
    import generator as G
    from reference import compare
    from reference import model as R

    entry = {w["name"]: w for w in cell.load_json(
        os.path.dirname(BENCH), "BENCHMARK.json")["workloads"]}[workload]
    cfg = cell.load_json(BENCH, "configs", f"{entry['config']}.json")
    traffic = cell.load_json(BENCH, "traffic", f"{entry['traffic']}.json")
    overrides = overrides or {}
    cfg.update(overrides.get("config", {}))
    traffic.update(overrides.get("traffic", {}))
    pool = G.make_pool(traffic, seed)
    order = G.visit_order(len(pool), seed)
    parts = [pool[order[i]] for i in range(3)]
    w_seed = G.weight_seed(seed)
    ref = R.train_steps(cfg, parts, w_seed)
    out = {"seed": seed}
    for name, kw in (("control", dict(precision="bfloat16")),
                     ("half_batch", dict(fault="half_batch")),
                     ("unchanged", dict(fault="unchanged"))):
        r = compare.readings(R.train_steps(cfg, parts, w_seed, **kw), ref)
        out[name] = r
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import cell
    cell.check_chip(1)
    cell.enable_cache()
    for seed in args.seeds:
        print(json.dumps(dict(workload=args.workload,
                              **readings_for(args.workload, seed))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
