"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: JAX describes a v5e:2x2 topology and the TPU compiler
compiles for its first device. That compile refuses what interpret mode
accepts — block shapes off the (8, 128) tiling, SMEM/VMEM overuse,
intermediates that do not fit HBM — so each kernel is compiled here at the
shapes the federated round and olmo-1b serving use, and each compiled
program must hold a Mosaic kernel (``tpu_custom_call``), not an
interpreted one.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.ternary import packed_nbytes
from repro.kernels.aggregate import packed_weighted_sum, padded_rows
from repro.kernels.quantize_pack import (
    BLOCK_S,
    LANES,
    quantize_pack_segments,
    quantize_pack_stacked,
    staged_rows,
)
from repro.kernels.ternary_matmul import ternary_matmul
from repro.kernels.vote import packed_vote_counts

RESNET_CONV = (3, 3, 64, 64)      # ResNet18*: 36,864 params → 2 grid blocks
OLMO_FF = (2048, 8192)            # olmo-1b w_in / w_gate: 512 grid blocks


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _assert_mosaic(lowered) -> None:
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("shape", [RESNET_CONV, OLMO_FF],
                         ids=["resnet_conv", "olmo_ff"])
def test_quantize_pack_segments_compiles_multi_block(one_chip, shape):
    n = 1
    for d in shape:
        n *= d
    rows = staged_rows(n)
    assert rows // BLOCK_S > 1
    staged = jax.ShapeDtypeStruct((rows, LANES), jnp.float32, sharding=one_chip)
    scal = jax.ShapeDtypeStruct((rows // BLOCK_S, 2), jnp.float32,
                                sharding=one_chip)
    _assert_mosaic(quantize_pack_segments.lower(staged, scal))


def test_quantize_pack_stacked_compiles(one_chip):
    theta = jax.ShapeDtypeStruct((16, 2048, 2048), jnp.float32,
                                 sharding=one_chip)
    per_layer = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda t, d, e: quantize_pack_stacked(t, d, e)[:2])
    _assert_mosaic(fn.lower(theta, per_layer, per_layer))


@pytest.mark.parametrize("kernel", [packed_weighted_sum, packed_vote_counts],
                         ids=["weighted_sum", "vote_counts"])
@pytest.mark.parametrize("n", [36_864, 2048 * 8192],
                         ids=["resnet_conv", "olmo_ff"])
def test_fanin_kernels_compile_at_16_clients(one_chip, kernel, n):
    stacked = jax.ShapeDtypeStruct((16, padded_rows(packed_nbytes(n)), LANES),
                                   jnp.uint8, sharding=one_chip)
    coeffs = jax.ShapeDtypeStruct((16,), jnp.float32, sharding=one_chip)
    _assert_mosaic(kernel.lower(stacked, coeffs))


@pytest.mark.parametrize("m,k,n", [(4, 2048, 8192), (128, 8192, 2048)],
                         ids=["olmo_decode", "olmo_prefill"])
def test_ternary_matmul_compiles(one_chip, m, k, n):
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k // 4, n), jnp.uint8, sharding=one_chip)
    w_q = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    _assert_mosaic(ternary_matmul.lower(x, w, w_q))
