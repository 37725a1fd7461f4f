"""Readings that set the upper end of the DeepGEN cell's limits.

    python3 bench/control_deepgen.py --seeds 11 12 13

``control.py``'s bfloat16 control and planted faults (``half_batch``,
``unchanged``), read with the DeepGEN reference (``reference/deepgen.py``)
at the cell's own size.  The benchmark's own runs never run this.  It
needs the accelerator; the CPU tests call ``readings_for`` at a small size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

WORKLOAD = "deepgen15-large-resident"


def readings_for(seed: int, overrides: dict = None,
                 workload: str = WORKLOAD) -> dict:
    import cell
    import generator as G
    from reference import compare
    from reference import deepgen as R

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
        out[name] = compare.readings(R.train_steps(cfg, parts, w_seed, **kw),
                                     ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import cell
    cell.check_chip(1)
    cell.enable_cache()
    for seed in args.seeds:
        print(json.dumps(dict(workload=WORKLOAD, **readings_for(seed))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
