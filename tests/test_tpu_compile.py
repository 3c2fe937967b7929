"""The Pallas kernels and the §V chunk program compile for a TPU v5e.

Nothing here runs: each test lowers with ``interpret=False`` and compiles
for a v5e that is described, not attached (`jax.experimental.topologies`),
so the TPU compiler refuses here what it would refuse on the chip: a
kernel over the scoped-VMEM limit, a misaligned block, a program that does
not fit. The topology is described inside a fixture, never at import, and
all of these tests live in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.api import RunSpec
from repro.api.runner import make_chunk_program
from repro.kernels import round_fused as rf

PAPER = dict(nodes=64, dim=10_000, mixer="ring", eps=1.0, clip_norm=1.0,
             calibration="coordinate", alpha0=1.0, schedule="sqrt_t",
             lam=1e-3, horizon=1562, stream="social_sparse")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # an entry compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shp, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)
    return make


def _compile_kernel(kernel, shape, m_pad, n_pad, block_cols=512):
    mat, vec, sc = (shape((m_pad, n_pad)), shape((m_pad,)), shape(()))
    if kernel == "round_stats":
        lowered = rf.round_stats.lower(mat, mat, sc, m_pad,
                                       block_cols=block_cols, interpret=False)
    elif kernel == "dual_step":
        lowered = rf.dual_step.lower(mat, mat, mat, vec, vec, sc,
                                     block_cols=block_cols, interpret=False)
    else:
        lowered = rf.round_update.lower(
            shape((m_pad, m_pad)), mat, mat, mat, mat, vec, vec, vec, sc, sc,
            True, block_cols=block_cols, interpret=False)
    return lowered.compile().as_text()


@pytest.mark.parametrize("kernel", rf.KERNELS)
@pytest.mark.parametrize("m,n", [(64, 10_000), (1024, 10_240)])
def test_kernel_compiles_for_v5e(shape, kernel, m, n):
    """§V widths and m=1024 compile at the block width the budget picks."""
    hlo = _compile_kernel(kernel, shape, rf._pad_rows(m), rf._pad_cols(n))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", rf.KERNELS)
def test_largest_budgeted_node_count_compiles(shape, kernel):
    """The most nodes the budget admits at 128-lane blocks compiles."""
    m_pad = rf.SUBLANE
    while rf.vmem_bytes(kernel, m_pad + rf.SUBLANE,
                        rf.LANE) <= rf.VMEM_LIMIT_BYTES:
        m_pad += rf.SUBLANE
    n_pad = 8 * rf.LANE
    assert rf.col_block(kernel, m_pad, n_pad, rf.LANE) == rf.LANE
    with pytest.raises(ValueError, match="scoped VMEM limit"):
        rf.col_block(kernel, m_pad + rf.SUBLANE, n_pad, rf.LANE)
    assert "tpu_custom_call" in _compile_kernel(kernel, shape, m_pad, n_pad,
                                                block_cols=rf.LANE)


def test_over_budget_shape_raises_before_lowering():
    """A node count no block width fits is the backend's ValueError, named
    by the limit, raised while the program is built — nothing is lowered."""
    with pytest.raises(ValueError, match="scoped VMEM limit"):
        rf.round_update(*(jnp.zeros((2048, 2048)),)
                        + (jnp.zeros((2048, 256)),) * 4
                        + (jnp.zeros((2048,)),) * 3,
                        jnp.float32(0.1), jnp.float32(0.0), True,
                        interpret=False)
    spec = RunSpec(nodes=4096, dim=256, horizon=4, backend="pallas",
                   backend_options={"interpret": False})
    with pytest.raises(ValueError, match="scoped VMEM limit"):
        make_chunk_program(spec, "sim")
    with pytest.raises(ValueError, match="scoped VMEM limit"):
        make_chunk_program(spec.replace(nodes=2048, backend_options={
            "mode": "fused", "interpret": False}), "sim")


def test_paper_chunk_program_compiles_for_v5e(shape):
    """The §V pallas chunk program, at a short chunk, holds the kernels."""
    spec = RunSpec(**PAPER, backend="pallas",
                   backend_options={"interpret": False})
    chunk_fn, init_fn = make_chunk_program(spec, "sim")
    state = jax.tree_util.tree_map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)))
    rounds = 4
    xs = shape((rounds, spec.nodes, spec.dim))
    ys = shape((rounds, spec.nodes))
    compiled = jax.jit(chunk_fn).lower(state, xs, ys).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3   # + node_sum
