"""Main-path Pallas kernels compile for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see the TPU compiler's
tiling and layout rules; these tests lower and compile each kernel at
the widths the served path uses, for one chip of a described ``v5e:2x2``
topology, with no chip attached.  The topology is described inside a
fixture (never at import) so every xdist worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.layout import LayoutSpec
from repro.kernels.distance_topk.kernel import distance_topk_pallas
from repro.kernels.gather_blocks.kernel import gather_blocks_pallas
from repro.kernels.quant_topk.kernel import quant_topk_pallas
from repro.kernels.quant_topk.ops import _quant_topk_jit

# the SIFT1M-shaped store: 128-d rows, L0 degree 16, 64 rows per block
SIFT_SPEC = LayoutSpec(dim=128, deg=16, np_max=4096, ov_cap=512,
                       slot_vecs=64, n_partitions=500)
N_ROWS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("d,group", [(128, 32), (960, 32)])
def test_quant_topk_compiles_for_v5e(one_chip, d, group):
    f = jax.jit(lambda q, c, s, nv: quant_topk_pallas(
        q, c, s, nv, jnp.int32(256), k=20, group=group))
    compiled = f.lower(_shape(one_chip, (256, d), jnp.float32),
                       _shape(one_chip, (N_ROWS, d), jnp.int8),
                       _shape(one_chip, (N_ROWS, d // group), jnp.float32),
                       _shape(one_chip, (), jnp.int32)).compile()
    assert _has_kernel(compiled)


def test_quant_topk_compiles_for_v5e_at_served_batch(one_chip):
    """The served call: a pow2 batch of 64 runs a 64-row q-tile."""
    f = jax.jit(lambda q, c, s, nv: _quant_topk_jit(
        q, c, s, 20, 32, nv, block_q=128, block_n=256, interpret=False,
        use_ref=False))
    compiled = f.lower(_shape(one_chip, (64, 128), jnp.float32),
                       _shape(one_chip, (N_ROWS, 128), jnp.int8),
                       _shape(one_chip, (N_ROWS, 128 // 32), jnp.float32),
                       _shape(one_chip, (), jnp.int32)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("width,dtype", [(SIFT_SPEC.vblk, jnp.float32),
                                         (SIFT_SPEC.gblk, jnp.int32)],
                         ids=["vblk", "gblk"])
def test_gather_blocks_compiles_for_v5e(one_chip, width, dtype):
    f = jax.jit(gather_blocks_pallas)
    compiled = f.lower(_shape(one_chip, (SIFT_SPEC.n_blocks, width), dtype),
                       _shape(one_chip, (8 * SIFT_SPEC.fetch_blocks,),
                              jnp.int32)).compile()
    assert _has_kernel(compiled)


def test_distance_topk_compiles_for_v5e(one_chip):
    f = jax.jit(lambda q, x, nv: distance_topk_pallas(q, x, nv, k=10))
    compiled = f.lower(_shape(one_chip, (256, 128), jnp.float32),
                       _shape(one_chip, (N_ROWS, 128), jnp.float32),
                       _shape(one_chip, (), jnp.int32)).compile()
    assert _has_kernel(compiled)
