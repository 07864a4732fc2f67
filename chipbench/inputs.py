"""Seeded images for the benchmark's traffic, made in bulk on the device.

One jitted call per image shape makes the whole pool of distinct images of
that shape: oriented sinusoidal ridges around a randomly placed whorl, plus
ink-noise texture, normalised to [0, 2**bit_depth - 1] per image, in the
container of that depth (`reference.pixel_dtype`). The same seed gives the
same pool. Only the program's outputs depend on pixel values; the work
of the REFMLM datapath does not.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from reference import pixel_dtype


def jax_seed(seed: int) -> int:
    """A 31-bit key seed derived from any whole-number `seed` (the benchmark's
    seeds may exceed what a 32-bit key holds)."""
    return int(np.random.default_rng(int(seed)).integers(1 << 31))


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _ridges(key, shape, count, top, dtype):
    h, w = shape
    k_centre, k_period, k_twist, k_noise = jax.random.split(key, 4)
    centre = jax.random.uniform(k_centre, (count, 2), minval=-0.125,
                                maxval=0.125)
    cy = (0.5 + centre[:, 0]) * h
    cx = (0.5 + centre[:, 1]) * w
    period = jax.random.uniform(k_period, (count,), minval=7.0, maxval=10.0)
    twist = jax.random.uniform(k_twist, (count,), minval=2.5, maxval=4.0)
    yy = jnp.arange(h, dtype=jnp.float32)[None, :, None] - cy[:, None, None]
    xx = jnp.arange(w, dtype=jnp.float32)[None, None, :] - cx[:, None, None]
    r = jnp.sqrt(yy * yy + xx * xx)
    theta = jnp.arctan2(yy, xx)
    ridges = jnp.sin(2 * jnp.pi / period[:, None, None] * r
                     + twist[:, None, None] * theta)
    ridges = ridges + 0.25 * jax.random.normal(k_noise, (count, h, w))
    lo = ridges.min(axis=(1, 2), keepdims=True)
    hi = ridges.max(axis=(1, 2), keepdims=True)
    scaled = (ridges - lo) / jnp.maximum(hi - lo, 1e-6) * float(top)
    return jnp.clip(jnp.round(scaled), 0, top).astype(dtype)


def image_pool(shape: tuple[int, int], count: int, seed: int,
               bit_depth: int = 8) -> np.ndarray:
    """(count, H, W) images of `bit_depth` bits on the host, made on the
    default device."""
    key = jax.random.PRNGKey(jax_seed(seed))
    return np.asarray(_ridges(key, (int(shape[0]), int(shape[1])), int(count),
                              (1 << int(bit_depth)) - 1,
                              pixel_dtype(bit_depth).name))


def image_pools(images: dict, count: int, seed: int,
                bit_depth: int = 8) -> dict[str, np.ndarray]:
    """One pool per named shape of a configuration, each from its own seed."""
    return {name: image_pool(tuple(hw), count, seed * 1009 + i, bit_depth)
            for i, (name, hw) in enumerate(sorted(images.items()))}
