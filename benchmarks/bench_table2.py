"""Table 2 analogue: DR-CircuitGNN vs homogeneous GCN/SAGE/GAT on
Mini-CircuitNet (synthetic) — congestion-prediction correlation scores."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.graphs.generator import generate_design
from repro.models.hgnn import homo_forward, homogenize, init_homo
from repro.optim import adamw_init, adamw_update
from repro.train import metrics as M
from repro.train.circuit_trainer import CircuitTrainConfig, CircuitTrainer


def train_homo(kind, graphs, test_graphs, epochs=6, hidden=64, lr=1e-3):
    homo = [homogenize(g) for g in graphs]
    homo_t = [homogenize(g) for g in test_graphs]
    f_in = homo[0][2].shape[1]
    params = init_homo(jax.random.PRNGKey(0), f_in, hidden, kind=kind)
    opt = adamw_init(params)

    def loss_fn(p, adj, adj_t, x, y, n_cell):
        pred = homo_forward(p, adj, adj_t, x, n_cell, kind=kind)
        return jnp.mean((pred - y) ** 2)

    step = jax.jit(jax.value_and_grad(loss_fn), static_argnums=(5,))
    for _ in range(epochs):
        for adj, adj_t, x, y, n_cell in homo:
            l, g = step(params, adj, adj_t, x, y, n_cell)
            params, opt = adamw_update(params, g, opt, jnp.asarray(lr),
                                       weight_decay=2e-4)
    preds, labels = [], []
    for adj, adj_t, x, y, n_cell in homo_t:
        preds.append(np.asarray(homo_forward(params, adj, adj_t, x, n_cell,
                                             kind=kind)))
        labels.append(np.asarray(y))
    return M.all_metrics(np.concatenate(preds), np.concatenate(labels))


def bench(scale=0.05, epochs=6):
    train = generate_design(0, "small", scale=scale)
    test = generate_design(99, "small", scale=scale)
    for kind in ("gcn", "sage", "gat"):
        m = train_homo(kind, train, test, epochs=epochs)
        emit(f"table2/{kind}", 0.0,
             f"pearson={m['pearson']:.3f};spearman={m['spearman']:.3f};"
             f"kendall={m['kendall']:.3f};mae={m['mae']:.3f};"
             f"rmse={m['rmse']:.3f}")
    tr = CircuitTrainer(CircuitTrainConfig(epochs=epochs, hidden=64,
                                           k_cell=16, k_net=16), 16, 16)
    out = tr.fit(train, eval_graphs=test)
    m = out["final"]
    emit("table2/dr-circuitgnn", 0.0,
         f"pearson={m['pearson']:.3f};spearman={m['spearman']:.3f};"
         f"kendall={m['kendall']:.3f};mae={m['mae']:.3f};"
         f"rmse={m['rmse']:.3f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    bench()
