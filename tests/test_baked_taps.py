"""Baked filter taps (DESIGN.md §7).

A conv kernel given concrete taps bakes them in as Python ints: a zero tap
adds nothing, and REFMLM runs the kom4 recursion with a constant operand,
so every base product whose coefficient digit is 0 is dropped. Traced taps
(a caller that jits over them) ride in SMEM and pay the full recursion.
Both must be byte-equal to each other and to the oracle in
`repro.filters.ref`, for every bank filter, both REFMLM bases, Mitchell's
multiplier (which the `fvc2004_mitchell` cell serves baked) and every
dataflow. The kernels run in interpret mode here.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.filters import FILTER_NAMES, apply_filter, conv, get_filter
from repro.filters.bank import max_intermediate
from repro.filters.conv import (
    KERNEL_METRICS,
    conv2d_pass,
    fused_separable_pass,
    second_pass_nbits,
)
from repro.filters.ref import apply_filter_ref

RNG = np.random.default_rng(11)
IMGS = jnp.asarray(RNG.integers(0, 256, (2, 9, 12)), jnp.int32)
METHODS = ("refmlm", "refmlm_nc", "mitchell")
CASES = [(name, method, flow)
         for name in FILTER_NAMES for method in METHODS
         for flow in (("direct", "two_pass", "fused")
                      if get_filter(name).separable else ("direct",))]


def _taps(spec, flow):
    if flow == "direct":
        return (np.asarray(spec.taps, np.int32),)
    return (np.asarray(spec.sep_row, np.int32)[None, :],
            np.asarray(spec.sep_col, np.int32)[:, None])


def _run(imgs, *taps, spec, method, flow):
    """One dataflow of `spec` through the recursion; taps static or traced."""
    kw = dict(method=method, interpret=True)
    if flow == "direct":
        return conv2d_pass(imgs, taps[0], nbits=8, shift=spec.shift,
                           post=spec.post, **kw)
    row, col = taps
    nb2 = second_pass_nbits(max_intermediate(spec),
                            int(np.abs(spec.sep_col).max()))
    if flow == "fused":
        return fused_separable_pass(imgs, row, col, nbits=8, nbits2=nb2,
                                    shift=spec.shift, post=spec.post, **kw)
    tmp = conv2d_pass(imgs, row, nbits=8, shift=0, post="none", **kw)
    return conv2d_pass(tmp, col, nbits=nb2, shift=spec.shift,
                       post=spec.post, **kw)


@functools.lru_cache(maxsize=None)
def _oracle(name, method, separable):
    return np.asarray(apply_filter_ref(IMGS, name, method=method,
                                       separable=separable))


@pytest.mark.parametrize("name,method,flow", CASES)
def test_baked_equals_traced_and_oracle(name, method, flow):
    spec = get_filter(name)
    taps = _taps(spec, flow)
    run = lambda x, *t: _run(x, *t, spec=spec, method=method, flow=flow)
    baked = np.asarray(run(IMGS, *taps))
    traced = np.asarray(jax.jit(run)(IMGS, *map(jnp.asarray, taps)))
    want = _oracle(name, method, flow != "direct")
    np.testing.assert_array_equal(baked, traced)
    np.testing.assert_array_equal(baked.astype(np.uint8), want)


def _leaves(**labels):
    return KERNEL_METRICS.gauge("conv_tap_leaves").value(**labels)


@pytest.mark.parametrize("name,row,col,baked,smem", [
    ("gaussian5", "1_4_6_4_1", "1/4/6/4/1", 72, 400),
    ("sobel_x", "-1_0_1", "1/2/1", 32, 240),
    ("sobel_y", "1_2_1", "-1/0/1", 28, 240),
    ("gaussian3", "4_8_4", "4/8/4", 36, 240),
])
def test_conv_tap_leaves_of_the_served_filters(name, row, col, baked, smem):
    """Base products per output pixel of the served fused kernels (8-bit
    row pass, 16-bit column pass): only the non-zero coefficient digits
    count when baked; every tap pays 16 + 64 on the SMEM path."""
    spec = get_filter(name)
    taps = _taps(spec, "fused")
    run = lambda x, *t: _run(x, *t, spec=spec, method="refmlm", flow="fused")
    run(IMGS, *taps)
    jax.jit(run)(IMGS, *map(jnp.asarray, taps))
    common = dict(kernel="refmlm_conv_fused", method="refmlm", nbits="8/16")
    kh, kw = len(spec.sep_col), len(spec.sep_row)
    assert _leaves(coef="baked", taps=f"{row} {col}", **common) == baked
    assert _leaves(coef="smem", taps=f"1x{kw} {kh}x1", **common) == smem


def test_direct_pass_records_its_own_kernel():
    taps = get_filter("laplacian").taps
    conv2d_pass(IMGS, taps, method="refmlm_nc", nbits=8, shift=0, post="abs",
                interpret=True)
    # taps 0,1,0 / 1,-4,1 / 0,1,0: five non-zero taps of one digit each
    assert _leaves(kernel="refmlm_conv_pass", coef="baked",
                   method="refmlm_nc", nbits="8",
                   taps="0_1_0/1_-4_1/0_1_0") == 5 * 4


@pytest.mark.parametrize("exec_kw", [
    dict(exec="sharded", mesh_shape=(1, 1)),
    dict(exec="streamed", tile=(8, 8)),
])
def test_distributed_paths_bake_their_taps(exec_kw, monkeypatch):
    """`repro.distribute` hands the passes host NumPy taps, so they bake
    them even inside shard_map, and stay byte-equal to the oracle."""
    builds = []
    record = conv._record_tap_leaves
    monkeypatch.setattr(conv, "_record_tap_leaves",
                        lambda *a: builds.append(a) or record(*a))
    jax.clear_caches()                  # trace the kernels afresh
    got = apply_filter(IMGS, "sobel_y", method="refmlm", interpret=True,
                       **exec_kw)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(apply_filter_ref(IMGS, "sobel_y")))
    assert builds and all(isinstance(taps, tuple)
                          for _, _, passes in builds for taps, _ in passes)
