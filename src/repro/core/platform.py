"""Backend detection + per-backend compiler parameters for the Pallas
kernels (DESIGN.md §7, §8).

Every kernel wrapper takes `interpret: bool | None`. `None` means
autodetect: compile for real on a TPU backend, fall back to the Pallas
interpreter elsewhere (the CPU containers this repo's tests run in). An
explicit True/False always wins -- interpret=True on TPU remains the
debugging escape hatch the Pallas guide recommends.

`grid_compiler_params` is the per-backend spelling of grid parallelism:
on a compiled TPU backend it returns `pltpu.CompilerParams` with the given
`dimension_semantics` tuple so independent grid axes actually parallelize
across megacores; under the interpreter (which executes the grid serially
and ignores Mosaic parameters) it returns None and the `pallas_call` is
issued without compiler params.

`enable_compile_cache` turns on JAX's persistent compilation cache for the
entry points that run on a chip (`chip_smoke.py`, the serving example, the
warmup CLI, the benchmark runner). Importing `repro` leaves it off.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: fixed persistent compile-cache directory at the repository root, used
#: when JAX_COMPILATION_CACHE_DIR is unset (the path is part of the cache
#: key, so it must not move between runs).
REPO_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def default_interpret() -> bool:
    """True unless the default JAX backend is a TPU (Pallas compiles there)."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Apply the interpret=None -> autodetect convention."""
    return default_interpret() if interpret is None else bool(interpret)


def grid_compiler_params(semantics: tuple[str, ...], interpret: bool):
    """dimension_semantics -> pallas_call compiler_params, gated per backend.

    `semantics` is one entry per grid axis, each 'parallel' or 'arbitrary'
    (reductions carried across grid steps must stay 'arbitrary').
    """
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu  # deferred: TPU-only path
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX has already read it and it
    stands; otherwise the cache goes to `REPO_COMPILE_CACHE`. Every
    compile is cached (the Pallas kernels compile in about a second each,
    under JAX's default one-second threshold). Call before the first
    compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_COMPILE_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


__all__ = ["REPO_COMPILE_CACHE", "default_interpret", "enable_compile_cache",
           "grid_compiler_params", "resolve_interpret"]
