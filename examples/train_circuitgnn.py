"""End-to-end driver: train DR-CircuitGNN for congestion prediction on
synthetic Mini-CircuitNet (the paper's Table 2 protocol, CPU scale).

    PYTHONPATH=src python examples/train_circuitgnn.py \
        [--epochs 10] [--scale 0.08] [--dense] [--k 16] \
        [--n-layers 15 --remat --wiring residual]

Deep backbones (DESIGN.md §13): ``--n-layers`` sets the stack depth (the
config's single source of truth), ``--wiring residual|dense`` adds skip
reuse from the second layer on, ``--remat`` checkpoints each layer so peak
training memory stops scaling with depth (stats prints the device's
``peak_memory_bytes``; the recomputed forward shows in a profiler trace
as a second run of the named ``drspmm_arena_fwd`` kernels).
"""

import argparse
import time

from repro.graphs.generator import generate_design
from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--scale", type=float, default=0.06)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--dense", action="store_true",
                    help="disable D-ReLU (dense baseline)")
    ap.add_argument("--n-train", type=int, default=4)
    ap.add_argument("--n-layers", type=int, default=2,
                    help="backbone depth (CircuitTrainConfig.n_layers)")
    ap.add_argument("--wiring", choices=("plain", "residual", "dense"),
                    default="plain", help="inter-layer reuse pattern")
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint each layer (constant-ish activation "
                         "memory in depth; backward recomputes forwards)")
    args = ap.parse_args()

    print("generating Mini-CircuitNet (synthetic)...")
    train = []
    for seed in range(args.n_train):
        train += generate_design(seed, "small", scale=args.scale)
    test = generate_design(999, "small", scale=args.scale)
    f_cell = train[0].x_cell.shape[1]
    f_net = train[0].x_net.shape[1]

    cfg = CircuitTrainConfig(epochs=args.epochs, hidden=args.hidden,
                             k_cell=args.k, k_net=args.k,
                             use_drelu=not args.dense,
                             n_layers=args.n_layers, wiring=args.wiring,
                             remat=args.remat)
    tr = CircuitTrainer(cfg, f_cell, f_net)
    t0 = time.perf_counter()
    out = tr.fit(train, eval_graphs=test)
    dt = time.perf_counter() - t0
    m = out["final"]
    mode = "dense" if args.dense else f"D-ReLU k={args.k}"
    depth = f"L={args.n_layers} {args.wiring}" \
            + (" remat" if args.remat else "")
    st = tr.stats()
    print(f"\n[{mode} {depth}] {dt:.1f}s  "
          f"Pearson={m['pearson']:.3f} Spearman={m['spearman']:.3f} "
          f"Kendall={m['kendall']:.3f} MAE={m['mae']:.3f} "
          f"RMSE={m['rmse']:.3f}  "
          f"peak={st['peak_memory_bytes'] / 1e6:.1f}MB")


if __name__ == "__main__":
    main()
