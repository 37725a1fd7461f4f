"""Hetero message-passing backend parity: topk vs pallas D-ReLU; the
pallas_fused vs xla_fused executors inside the full layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hetero_mp import HeteroMPConfig, hetero_conv, init_hetero_layer
from repro.graphs.generator import generate_design


@pytest.fixture(scope="module")
def setup():
    g = generate_design(7, "small", scale=0.03)[0]
    params = init_hetero_layer(jax.random.PRNGKey(0), 16)
    rng = np.random.default_rng(0)
    xc = jnp.asarray(rng.normal(size=(g.n_cell, 16)).astype(np.float32))
    xn = jnp.asarray(rng.normal(size=(g.n_net, 16)).astype(np.float32))
    return g, params, xc, xn


def test_pallas_drelu_backend_matches_topk(setup):
    g, params, xc, xn = setup
    base = HeteroMPConfig(hidden=16, k_cell=4, k_net=4)
    pall = HeteroMPConfig(hidden=16, k_cell=4, k_net=4,
                          drelu_backend="pallas")
    yc0, yn0 = hetero_conv(params, g, xc, xn, base)
    yc1, yn1 = hetero_conv(params, g, xc, xn, pall)
    np.testing.assert_allclose(np.asarray(yc0), np.asarray(yc1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(yn0), np.asarray(yn1),
                               rtol=1e-5, atol=1e-5)


def test_pallas_spmm_backend_in_layer(setup):
    """On the plan path and on the serial per-relation path."""
    g, params, xc, xn = setup
    for use_plan in (True, False):
        a = HeteroMPConfig(hidden=16, k_cell=4, k_net=4, backend="xla_fused",
                           use_plan=use_plan)
        b = HeteroMPConfig(hidden=16, k_cell=4, k_net=4,
                           backend="pallas_fused", use_plan=use_plan)
        for ya, yb in zip(hetero_conv(params, g, xc, xn, a),
                          hetero_conv(params, g, xc, xn, b)):
            np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                                       rtol=1e-5, atol=1e-5,
                                       err_msg=f"use_plan={use_plan}")


def test_pallas_drelu_gradients_flow(setup):
    g, params, xc, xn = setup
    cfg = HeteroMPConfig(hidden=16, k_cell=4, k_net=4,
                         drelu_backend="pallas")

    def f(x):
        yc, yn = hetero_conv(params, g, x, xn, cfg)
        return jnp.sum(yc ** 2)

    gx = jax.grad(f)(xc)
    assert np.isfinite(np.asarray(gx)).all()
    assert float(jnp.abs(gx).sum()) > 0