"""Compile guards: every main-path Pallas kernel must compile natively
(``interpret=False``) for TPU v5e at full-scale shapes.

The TPU compiler compiles for a described topology without a chip attached,
so these catch what interpret mode cannot: ops Mosaic refuses to lower,
unaligned slices, and VMEM blocks too large for the chip.  Shapes are the
``large`` design of Table 1 at ``scale=1.0`` (graphs/generator.py): a
type-concat source slab of 9816 cells + 9100 nets, and a relation-fused
super-arena of that partition (about 18.5k chunks of 8×4 neighbour slots).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist only the worker running this file does.  Each compile is
traced under an abstract mesh of the described chip, so that what the
kernels ask of the device (``pltpu.get_tpu_info``: the VMEM that decides
the arena gather's path) is the v5e's, as in a run on the chip.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.graphs.ell import FusedELL
from repro.kernels import drspmm as K
from repro.kernels.drelu_topk import drelu_pallas

from _gather import path_of

N_SRC = 9816 + 9100          # type-concat CBSR slab (cells + nets)
N_ARENA = 28_000             # super-arena output rows
N_CHUNKS = 18_500            # super-arena chunks
BR, EC = 8, 4                # chunk geometry pick_chunk_multi chooses there
K_KEEP = 16                  # D-ReLU k
N_DENSE = 1000               # dense-tier relation-concat rows


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                 # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _super_arena(sharding) -> FusedELL:
    s = lambda shape, dt: _sds(sharding, shape, dt)
    return FusedELL(
        nbr=s((N_CHUNKS, BR, EC), jnp.int32),
        w=s((N_CHUNKS, BR, EC), jnp.float32),
        block_of=s((N_CHUNKS,), jnp.int32),
        start=s((N_CHUNKS,), jnp.int32),
        rows=s((N_ARENA,), jnp.int32),
        gather=s((N_ARENA,), jnp.int32),
        n_dst=N_ARENA, n_src=N_SRC, nnz=-1, row_block=BR, chunk=EC,
        rel=s((N_CHUNKS,), jnp.int32))


def _compile(fn, *args):
    """Native compile for the described chip; the HLO must carry the
    Mosaic kernel (an interpreted kernel would lower to plain HLO)."""
    devices = {d for a in jax.tree.leaves(args) for d in a.sharding.device_set}
    mesh = jax.sharding.Mesh(np.array(sorted(devices, key=lambda d: d.id)),
                             ("chip",))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _compiled_path(fn, *args) -> str:
    """Compile ``fn`` and name the gather path its arena kernel took."""
    return path_of(_compile, fn, *args)[1]


@pytest.mark.parametrize("hidden", [64, 128, 256])   # 256: D-tiled grid
def test_drspmm_fwd_multi_compiles(one_chip, hidden):
    """The type-concat CBSR slab (9.7 MB) is VMEM-resident on a v5e."""
    assert _compiled_path(
        lambda f, v, i: K.drspmm_fwd_multi(f, v, i, hidden, interpret=False),
        _super_arena(one_chip),
        _sds(one_chip, (N_SRC, K_KEEP), jnp.float32),
        _sds(one_chip, (N_SRC, K_KEEP), jnp.int32)) == "vmem"


@pytest.mark.parametrize("hidden", [64, 128, 256])
def test_drspmm_bwd_multi_compiles(one_chip, hidden):
    """So is the lane-padded dY (14.3 MB at hidden 128, 28.7 MB at 256)."""
    assert _compiled_path(
        lambda f, rows, gy, i: K.drspmm_bwd_multi(f, rows, gy, i,
                                                  interpret=False),
        _super_arena(one_chip),
        _sds(one_chip, (N_ARENA,), jnp.int32),
        _sds(one_chip, (N_ARENA, hidden), jnp.float32),
        _sds(one_chip, (N_SRC, K_KEEP), jnp.int32)) == "vmem"


def test_drspmm_fwd_over_budget_compiles_on_dma_path(one_chip):
    """A source slab past the VMEM budget (140k type-concat rows, 71.7 MB
    against half of the v5e's 128 MiB) keeps the per-row DMA gather, whose
    VMEM does not grow with the source."""
    n_src = 140_000
    arena = dataclasses.replace(_super_arena(one_chip), n_src=n_src)
    assert _compiled_path(
        lambda f, v, i: K.drspmm_fwd_multi(f, v, i, 64, interpret=False),
        arena, _sds(one_chip, (n_src, K_KEEP), jnp.float32),
        _sds(one_chip, (n_src, K_KEEP), jnp.int32)) == "dma"


def test_widest_fused_chunk_compiles(one_chip):
    """A fused chunk of 8 × 16 slots, the widest ``pick_chunk`` makes:
    its rows leave the slab through the same loop of 8-row tiles as the
    8 × 4 chunk's, sixteen trips in place of four."""
    arena = dataclasses.replace(
        _super_arena(one_chip), chunk=16,
        nbr=_sds(one_chip, (N_CHUNKS // 4, BR, 16), jnp.int32),
        w=_sds(one_chip, (N_CHUNKS // 4, BR, 16), jnp.float32),
        block_of=_sds(one_chip, (N_CHUNKS // 4,), jnp.int32),
        start=_sds(one_chip, (N_CHUNKS // 4,), jnp.int32),
        rel=_sds(one_chip, (N_CHUNKS // 4,), jnp.int32))
    assert _compiled_path(
        lambda f, v, i: K.drspmm_fwd_multi(f, v, i, 64, interpret=False),
        arena, _sds(one_chip, (N_SRC, K_KEEP), jnp.float32),
        _sds(one_chip, (N_SRC, K_KEEP), jnp.int32)) == "vmem"


def test_dense_tier_fwd_compiles(one_chip):
    _compile(lambda a, v, i: K.drspmm_dense_tier_fwd(a, v, i, 64,
                                                     interpret=False),
             _sds(one_chip, (N_DENSE, N_SRC), jnp.float32),
             _sds(one_chip, (N_SRC, K_KEEP), jnp.float32),
             _sds(one_chip, (N_SRC, K_KEEP), jnp.int32))


def test_dense_tier_bwd_compiles(one_chip):
    _compile(lambda at, gy, i: K.drspmm_dense_tier_bwd(at, gy, i,
                                                       interpret=False),
             _sds(one_chip, (N_SRC, N_DENSE), jnp.float32),
             _sds(one_chip, (N_DENSE, 64), jnp.float32),
             _sds(one_chip, (N_SRC, K_KEEP), jnp.int32))


@pytest.mark.parametrize("hidden", [64, 128])
def test_drelu_pallas_compiles(one_chip, hidden):
    _compile(lambda x: drelu_pallas(x, K_KEEP, interpret=False),
             _sds(one_chip, (9800, hidden), jnp.float32))


def test_default_branch_is_native_for_tpu(one_chip):
    """With ``interpret`` left to the kernel, a program lowered for the
    described TPU takes the native branch — the same one a chip run takes."""
    arena = _super_arena(one_chip)
    lowered = jax.jit(lambda f, v, i: K.drspmm_fwd_multi(f, v, i, 64)).lower(
        arena, _sds(one_chip, (N_SRC, K_KEEP), jnp.float32),
        _sds(one_chip, (N_SRC, K_KEEP), jnp.int32))
    assert "tpu_custom_call" in lowered.as_text()
    # ...while the same call on this host's CPU runs interpreted
    f = FusedELL(nbr=np.zeros((1, BR, EC), np.int32),
                 w=np.ones((1, BR, EC), np.float32),
                 block_of=np.zeros(1, np.int32), start=np.ones(1, np.int32),
                 rows=np.zeros(BR, np.int32), gather=np.zeros(BR, np.int32),
                 n_dst=BR, n_src=1, nnz=-1, row_block=BR, chunk=EC,
                 rel=np.zeros(1, np.int32))
    y = K.drspmm_fwd_multi(f, jnp.ones((1, 2)), jnp.asarray([[0, 3]]), 8)
    np.testing.assert_allclose(np.asarray(y), np.tile(
        [[EC, 0, 0, EC, 0, 0, 0, 0]], (BR, 1)))


def _eid_arena(sharding) -> FusedELL:
    return dataclasses.replace(
        _super_arena(sharding), rel=None,
        eid=_sds(sharding, (N_CHUNKS, BR, EC), jnp.int32))


def test_learnable_dw_compiles(one_chip):
    """The per-slot dL/dw kernel.  The learnable forward and dx run the
    fixed-weight kernels above on weights gathered into arena order by XLA
    (DESIGN.md §8.2), so they add no kernel of their own."""
    assert _compiled_path(
        lambda f, g, v, i: K.drspmm_dw_learnable_fused(f, g, v, i,
                                                       interpret=False),
        _eid_arena(one_chip),
        _sds(one_chip, (N_ARENA, 64), jnp.float32),
        _sds(one_chip, (N_SRC, K_KEEP), jnp.float32),
        _sds(one_chip, (N_SRC, K_KEEP), jnp.int32)) == "vmem"


@pytest.mark.parametrize("hidden", [128, 256])
def test_gen_aggr_fwd_compiles(one_chip, hidden):
    """DeepGEN's softmax-aggregation forward: a whole lane-padded message
    row per edge, slot-major in VMEM."""
    arena = _super_arena(one_chip)
    _compile(lambda f, nbr, t, m: K.gen_aggr_fwd(f, nbr, t, m,
                                                 interpret=False),
             arena, arena.nbr, _sds(one_chip, (3,), jnp.float32),
             _sds(one_chip, (N_SRC + 1, 1, hidden), jnp.float32))


@pytest.mark.parametrize("hidden", [128, 256])
def test_gen_aggr_bwd_compiles(one_chip, hidden):
    """Its transposed backward: one [g | a | lse] row per edge, kept one
    DMA by the table's unit axis."""
    arena = _super_arena(one_chip)
    _compile(lambda f, nbr, t, y, m: K.gen_aggr_bwd(f, nbr, t, y, m,
                                                    interpret=False),
             arena, arena.nbr, _sds(one_chip, (3,), jnp.float32),
             _sds(one_chip, (N_ARENA + 1, 1, 3 * hidden), jnp.float32),
             _sds(one_chip, (N_ARENA, hidden), jnp.float32))
