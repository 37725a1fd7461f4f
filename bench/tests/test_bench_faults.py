"""A whole run on the CPU at a small size with the timed path broken
underneath (the chip check skipped): each fault a training cell can have
makes ``correct`` come out false."""

import pytest

import cell

SMALL = {"traffic": {"scale": 0.03}, "config": {"backend": "xla_fused"}}
CELLS = ("drcgnn-large-resident", "drcgnn-table1-stream")


def _unchanged(monkeypatch):
    monkeypatch.setattr("repro.train.circuit_trainer.adamw_update",
                        lambda params, grads, state, lr, **kw: (params, state))


def _half_batch(monkeypatch):
    import jax.numpy as jnp
    from repro.models.hgnn import drcircuitgnn_forward

    def half_loss(params, graph, cfg, spec=None):
        pred = drcircuitgnn_forward(params, graph, cfg, spec)
        n = pred.shape[0] // 2
        return jnp.mean((pred[:n] - graph.y_cell[:n]) ** 2)

    monkeypatch.setattr("repro.train.circuit_trainer.loss_fn", half_loss)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    r = cell.run_cell(workload, 31, 0.3, False, require_chip=False,
                      overrides=SMALL)
    assert r["correct"] is False
