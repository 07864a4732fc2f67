"""The main path's Pallas kernels compile for a TPU v5e, without the chip.

The topology is described (not attached), so each test compiles exactly
what the chip's compiler would receive and fails where Mosaic would refuse
it: a lowering it does not support (the KCM table gather), a block that
overflows the 16 MiB scoped VMEM, a dtype the MXU does not take. Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.filters import apply_filter, get_filter
from repro.filters.conv import conv2d_pass, fused_separable_pass
from repro.kernels.karatsuba_matmul import karatsuba_matmul_kernel
from repro.kernels.mitchell_matmul import mitchell_matmul_kernel
from repro.tuning import default_blocks

G3 = np.asarray(get_filter("gaussian3").taps)
G5 = get_filter("gaussian5")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                 # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def assert_mosaic(text: str) -> None:
    assert "tpu_custom_call" in text


def test_direct_3x3_refmlm_tiled(one_chip):
    """FVC2004 DB1 batch: 640 columns tile at 256 with paired views."""
    assert default_blocks("direct", 8, 480, 640, 3, 3,
                          interpret=False).block_cols == 256
    assert_mosaic(compiled_text(
        lambda x: conv2d_pass(x, G3, method="refmlm", interpret=False),
        one_chip, ((8, 480, 640), jnp.int32)))


def test_fused_5x5_camera_frame(one_chip):
    assert_mosaic(compiled_text(
        lambda x: fused_separable_pass(x, G5.sep_row, G5.sep_col,
                                       method="refmlm", nbits2=16,
                                       interpret=False),
        one_chip, ((1, 1080, 1920), jnp.int32)))


def test_16_bit_second_pass(one_chip):
    """The separable column pass multiplies at 16 bits, the heaviest
    REFMLM recursion of the datapath."""
    col = np.asarray(G5.sep_col)[:, None]
    assert_mosaic(compiled_text(
        lambda x: conv2d_pass(x, col, method="refmlm", nbits=16,
                              interpret=False),
        one_chip, ((8, 480, 640), jnp.int32)))


@pytest.mark.parametrize("kind", ["direct", "fused"])
def test_folded_small_batch_default_blocks(one_chip, kind):
    """The heuristic's folded band for a served 8 x 128 x 128 batch fits
    the scoped VMEM (its uncapped 520-row band needed 27.8 MiB of 16)."""
    cfg = default_blocks(kind, 8, 128, 128, 3, 3, interpret=False)
    assert cfg.batch_fold
    g3 = get_filter("gaussian3")
    fn = ((lambda x: conv2d_pass(x, G3, interpret=False)) if kind == "direct"
          else (lambda x: fused_separable_pass(x, g3.sep_row, g3.sep_col,
                                               nbits2=16, interpret=False)))
    assert_mosaic(compiled_text(fn, one_chip, ((8, 128, 128), jnp.int32)))


def test_mitchell_matmul_kernel(one_chip):
    assert_mosaic(compiled_text(
        lambda a, b: mitchell_matmul_kernel(a, b, interpret=False),
        one_chip, ((64, 256), jnp.int32), ((256, 128), jnp.int32)))


@pytest.mark.parametrize("karatsuba", [True, False])
def test_karatsuba_matmul_kernel(one_chip, karatsuba):
    assert_mosaic(compiled_text(
        lambda ah, al, bh, bl: karatsuba_matmul_kernel(
            ah, al, bh, bl, karatsuba=karatsuba, interpret=False),
        one_chip, ((128, 256), jnp.int32), ((128, 256), jnp.int32),
        ((256, 128), jnp.int32), ((256, 128), jnp.int32)))


@pytest.mark.parametrize("name", ["gaussian5", "sobel_x", "sharpen3"])
def test_auto_never_takes_the_kcm_gather(one_chip, name):
    """Default arguments on a compiled pass resolve to the recursion, so
    the unlowerable 1-D ROM gather never reaches Mosaic."""
    assert_mosaic(compiled_text(
        lambda x: apply_filter(x, name, interpret=False),
        one_chip, ((2, 64, 128), jnp.uint8)))
