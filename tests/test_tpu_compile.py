"""The main path's Pallas kernels compile for a TPU v5e, without the chip.

The topology is described (not attached), so each test compiles exactly
what the chip's compiler would receive and fails where Mosaic would refuse
it: a lowering it does not support, a block that overflows the 16 MiB
scoped VMEM, a dtype the MXU does not take. Nothing
runs, so these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
import re

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


def test_16_bit_container_fused_gaussian5(one_chip):
    """A served batch of 8 CT slices of 512 x 512 in uint16: the 16-bit
    row pass and the 32-bit column pass of gaussian5 in the one fused
    kernel, baked taps, answered in uint16."""
    text = compiled_text(
        lambda x: apply_filter(x, "gaussian5", method="refmlm", nbits=16,
                               container="uint16", interpret=False),
        one_chip, ((8, 512, 512), jnp.int32))
    assert_mosaic(text)
    assert "%refmlm_conv_fused" in text
    assert coef_operands(text, "refmlm_conv_fused") == []
    assert re.search(r"ROOT \S+ = u16\[8,512,512\]", text)


@pytest.mark.parametrize("name,method,nbits,shape,dtype", [
    ("gaussian5", "refmlm", 8, (8, 480, 640), jnp.uint8),
    ("sobel_y", "mitchell", 8, (8, 480, 640), jnp.uint8),
    ("gaussian3", "refmlm", 8, (1, 1080, 1920), jnp.uint8),
    ("gaussian5", "refmlm", 16, (8, 512, 512), jnp.uint16),
])
def test_served_batch_compiles_in_its_container(one_chip, name, method,
                                                nbits, shape, dtype):
    """A served batch reaches the device in its pixel container and is
    widened to int32 on the device, ahead of the kernel."""
    text = compiled_text(
        lambda x: apply_filter(x, name, method=method, nbits=nbits,
                               interpret=False),
        one_chip, (shape, dtype))
    assert_mosaic(text)
    wire = "u8" if dtype == jnp.uint8 else "u16"
    dims = ",".join(map(str, shape))
    assert re.search(rf"%x\S* = {wire}\[{dims}\]\S* parameter\(0\)", text)


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


def coef_operands(text: str, kernel: str) -> list[str]:
    """The rank-2 operands (coefficient tables) of the `kernel` custom call:
    image views are rank 3, so a kernel with baked taps has none."""
    line = next(ln for ln in text.splitlines()
                if f"%{kernel}" in ln and "custom-call(" in ln)
    shapes = re.search(r"operand_layout_constraints=\{(.*?\})\}", line)[1]
    return re.findall(r"s32\[\d+,\d+\]", shapes)


SERVED = {"gaussian5": (8, 480, 640), "gaussian3": (1, 1080, 1920),
          "sobel_x": (8, 480, 640)}


@pytest.mark.parametrize("name", list(SERVED))
@pytest.mark.parametrize("kind", ["direct", "fused"])
def test_baked_taps_compile_under_the_kernel_names(one_chip, name, kind):
    """Concrete taps are baked into the kernel: no coefficient operand
    reaches the custom call, whose name the device trace reads."""
    spec = get_filter(name)
    if kind == "direct":
        kernel = "refmlm_conv_pass"
        fn = lambda x: conv2d_pass(x, spec.taps, method="refmlm",
                                   shift=spec.shift, post=spec.post,
                                   interpret=False)
    else:
        kernel = "refmlm_conv_fused"
        fn = lambda x: fused_separable_pass(x, spec.sep_row, spec.sep_col,
                                            method="refmlm", nbits2=16,
                                            shift=spec.shift, post=spec.post,
                                            interpret=False)
    text = compiled_text(fn, one_chip, (SERVED[name], jnp.int32))
    assert_mosaic(text)
    assert coef_operands(text, kernel) == []


@pytest.mark.parametrize("kind", ["direct", "fused"])
def test_traced_taps_compile_on_the_smem_path(one_chip, kind):
    """Taps passed as jit arguments stay run-time SMEM operands."""
    if kind == "direct":
        kernel, taps = "refmlm_conv_pass", [((3, 3), jnp.int32)]
        fn = lambda x, t: conv2d_pass(x, t, method="refmlm", interpret=False)
        want = ["s32[3,3]"]
    else:
        kernel, taps = "refmlm_conv_fused", [((3,), jnp.int32)] * 2
        fn = lambda x, r, c: fused_separable_pass(x, r, c, method="refmlm",
                                                  nbits2=16, interpret=False)
        want = ["s32[1,3]", "s32[3,1]"]
    text = compiled_text(fn, one_chip, ((8, 480, 640), jnp.int32), *taps)
    assert coef_operands(text, kernel) == want


@pytest.mark.parametrize("name", ["refmlm_conv_pass", "refmlm_conv_fused",
                                  "mitchell_matmul", "karatsuba_matmul"])
def test_kernels_carry_stable_names(one_chip, name):
    """Each Pallas kernel names its custom call, so a device trace reads
    the kernel's name, not the private function that launched it."""
    i32 = jnp.int32
    fn, shapes = {
        "refmlm_conv_pass": (
            lambda x: conv2d_pass(x, G3, method="refmlm", interpret=False),
            [((1, 64, 128), i32)]),
        "refmlm_conv_fused": (
            lambda x: fused_separable_pass(x, G5.sep_row, G5.sep_col,
                                           nbits2=16, interpret=False),
            [((1, 64, 128), i32)]),
        "mitchell_matmul": (
            lambda a, b: mitchell_matmul_kernel(a, b, interpret=False),
            [((64, 256), i32), ((256, 128), i32)]),
        "karatsuba_matmul": (
            lambda ah, al, bh, bl: karatsuba_matmul_kernel(
                ah, al, bh, bl, interpret=False),
            [((128, 256), i32)] * 2 + [((256, 128), i32)] * 2),
    }[name]
    text = compiled_text(fn, one_chip, *shapes)
    assert f"%{name}" in text and "tpu_custom_call" in text
