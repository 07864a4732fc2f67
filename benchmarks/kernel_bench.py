"""Pallas kernel micro-benchmarks (interpret mode on CPU: relative numbers
only -- the TPU roofline terms for these kernels come from the dry-run).

Reports us/call + achieved element-throughput for the three kernels across
block-size variants, plus the batched filter-bank pipeline across filters x
batch sizes and the before/after pairs of DESIGN.md §7/§8:

  * fused-vs-two-pass     -- one-kernel separable (VMEM halo band) vs two
                             kernels with an HBM int32 intermediate;
  * separable-vs-direct   -- kh+kw vs kh*kw tap products per pixel;
  * fold-vs-serial-batch  -- batch folded into the parallel row-tile axis
                             vs the serial leading batch axis (§8);
  * scratch-vs-output     -- matmul K reduction carried in a VMEM scratch
                             tile vs in-place output accumulation (§8).

Block shapes default through the per-backend autotune cache
(`repro.tuning`); regenerate it with `python -m repro.tuning.autotune`
before a bench run on a new platform.

Distribution rows (DESIGN.md §9): local vs sharded (shard_map over the
host-device mesh -- requires the process to start with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; with one visible
device the sharded rows are skipped) vs streamed (out-of-core tiles) on
the n=32 batch, with bit-identity recorded alongside throughput.

``--smoke`` runs the reduced-size regression guard used by
scripts/check.sh: batched throughput (n=8) must not fall below
single-image throughput for any guarded bank filter. ``--smoke-dist`` is the multi-device guard:
sharded output must be bit-identical to local and sharded n=32 throughput
must not fall below local n=32 on any guarded filter. ``--smoke-tune`` is
the §11 plan-tuning guard: a pruned replay of an exhaustive sweep must
keep the same winner while timing strictly fewer candidates.
"""
from __future__ import annotations

import sys

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn, write_bench_json
from repro.filters import apply_filter, resolve_filter_plan
from repro.kernels.ops import gaussian_filter, gaussian_kernel_3x3, limb_matmul, lns_matmul

#: bank filters under the batch-scaling smoke guard (n=8 must beat n=1).
SCALING_GUARD_FILTERS = ("gaussian3", "gaussian5")

#: bank filters under the sharded-throughput smoke guard (sharded n=32 must
#: not lose to local n=32; DESIGN.md §9).
DIST_GUARD_FILTERS = ("gaussian5",)


def _img_batch(rng, batch: int, h: int = 128, w: int = 128):
    """Uniform uint8-range image batch as the int32 the datapath expects."""
    return jnp.asarray(rng.integers(0, 256, (batch, h, w)), jnp.int32)


def _bank_variants(imgs, *, tag: str):
    """The §7 dataflow pairs on the 5x5 Gaussian refmlm path: direct
    (kh·kw taps), two-pass and fused separable (kh+kw taps)."""
    npix = imgs.shape[0] * imgs.shape[1] * imgs.shape[2]
    out = {}
    for name, kw in (("direct", dict(separable=False)),
                     ("two_pass", dict(separable=True, fused=False)),
                     ("fused", dict(separable=True, fused=True))):
        us = time_fn(lambda x: apply_filter(x, "gaussian5", method="refmlm",
                                            **kw),
                     imgs, iters=3)
        row = name if name == "direct" else f"sep_{name}"
        emit(f"kernel_{tag}gaussian5_{row}", us, f"mpix_s={npix/us:.2f}")
        out[name] = us
    emit(f"kernel_{tag}gaussian5_fused_speedup",
         out["two_pass"] / out["fused"], "x_vs_two_pass")
    # §11: the default call resolves the per-shape plan (the cached one
    # where the backend has a tuning cache, else the heuristic). Report it
    # against the best forced row of a *different* dataflow so the speedup
    # reads "dataflow winner vs best losing alternative".
    plan = resolve_filter_plan("gaussian5", *imgs.shape, method="refmlm")
    us = time_fn(lambda x: apply_filter(x, "gaussian5", method="refmlm"),
                 imgs, iters=3)
    emit(f"kernel_{tag}gaussian5_dataflow_winner", us,
         f"mpix_s={npix/us:.2f}", dataflow=plan.dataflow)
    out["winner"] = us
    best_loser = min(us for df, us in out.items()
                     if df not in (plan.dataflow, "winner"))
    emit(f"kernel_{tag}gaussian5_winner_speedup", best_loser / us,
         "x_vs_best_losing_dataflow")
    return out


def _bank_scaling(rng, *, tag: str, h: int = 128, w: int = 128,
                  filters=("gaussian3", "gaussian5", "sobel_x")):
    """Filter-bank batch-scaling sweep (§8): autotuned grid per batch size,
    plus the fold-vs-serial-batch before/after at n=8. Returns
    filter -> {batch: mpix_s} for the smoke guard."""
    mpix = {}
    for filt in filters:
        mpix[filt] = {}
        for batch in (1, 4, 8):
            imgs = _img_batch(rng, batch, h, w)
            us = time_fn(lambda x: apply_filter(x, filt, method="refmlm"),
                         imgs, iters=3)
            mpix[filt][batch] = batch * h * w / us
            emit(f"kernel_{tag}{filt}_n{batch}", us,
                 f"mpix_s={mpix[filt][batch]:.2f}")
        imgs = _img_batch(rng, 8, h, w)
        us = time_fn(lambda x: apply_filter(x, filt, method="refmlm",
                                            batch_fold=False), imgs, iters=3)
        emit(f"kernel_{tag}{filt}_n8_nofold", us,
             f"mpix_s={8*h*w/us:.2f}")
        emit(f"kernel_{tag}{filt}_fold_speedup",
             us / (8 * h * w / mpix[filt][8]), "x_vs_serial_batch_n8")
        emit(f"kernel_{tag}{filt}_batch_scaling",
             mpix[filt][8] / mpix[filt][1], "x_mpix_n8_vs_n1")
    return mpix


def _dist_variants(rng, *, tag: str, n: int = 32, h: int = 128, w: int = 128,
                   filt: str = "gaussian5"):
    """The §9 execution-mode rows: local vs sharded vs streamed on one
    batch, bit-identity recorded with the throughput. Returns
    mode -> {us, mpix_s, identical} for the smoke guard."""
    from repro import distribute

    imgs = _img_batch(rng, n, h, w)
    npix = n * h * w
    out = {}

    def run(mode, fn, **fields):
        ref = np.asarray(fn())
        identical = bool((ref == np.asarray(out["local"]["out"])).all()) \
            if "local" in out else True
        us = time_fn(fn, iters=3)
        mpix = round(npix / us, 2)
        emit(f"kernel_{tag}{filt}_{mode}_n{n}", us, exec=mode,
             mpix_s=mpix, bit_identical=identical, **fields)
        out[mode] = {"us": us, "mpix_s": mpix, "identical": identical,
                     "out": ref}
        return us

    run("local", lambda: apply_filter(imgs, filt, method="refmlm"))
    ndev = distribute.device_count()
    if ndev >= 2:
        run("sharded", lambda: apply_filter(imgs, filt, method="refmlm",
                                            exec="sharded", devices=ndev),
            devices=ndev)
        emit(f"kernel_{tag}{filt}_sharded_speedup",
             out["local"]["us"] / out["sharded"]["us"],
             x_vs_local=round(out["local"]["us"] / out["sharded"]["us"], 2))
    else:
        print(f"# skipping kernel_{tag}{filt}_sharded rows: 1 visible device "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    src = np.asarray(imgs, np.uint8)
    run("streamed", lambda: apply_filter(src, filt, method="refmlm",
                                         exec="streamed", tile=(64, 64)),
        tile="64x64")
    return out


def main():
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(256, 256)), jnp.float32)
    flops = 2 * 128 * 256 * 256

    matmul_us = {}
    for bm in (16, 32):
        us = time_fn(lambda x, y: lns_matmul(x, y, block_m=bm), lhs, rhs, iters=3)
        matmul_us[f"lns_bm{bm}"] = us
        emit(f"kernel_lns_matmul_bm{bm}", us, f"gflops={flops/us/1e3:.3f}")
    # §8 before/after: VMEM-scratch reduction carry vs in-place output.
    us = time_fn(lambda x, y: lns_matmul(x, y, block_m=16, accum="output"),
                 lhs, rhs, iters=3)
    emit("kernel_lns_matmul_bm16_outacc", us, f"gflops={flops/us/1e3:.3f}")
    emit("kernel_lns_matmul_scratch_speedup",
         us / matmul_us["lns_bm16"], "x_vs_output_accum")
    for ecc in (1, 3):
        us = time_fn(lambda x, y: lns_matmul(x, y, num_ecc=ecc, case_split=False),
                     lhs, rhs, iters=3)
        emit(f"kernel_lns_matmul_ecc{ecc}", us, f"gflops={flops/us/1e3:.3f}")
    for kar in (True, False):
        us = time_fn(lambda x, y: limb_matmul(x, y, karatsuba=kar), lhs, rhs,
                     iters=3)
        matmul_us[f"limb_{kar}"] = us
        emit(f"kernel_limb_matmul_{'kom3' if kar else 'kom4'}", us,
             f"gflops={flops/us/1e3:.3f}")
    us = time_fn(lambda x, y: limb_matmul(x, y, accum="output"), lhs, rhs,
                 iters=3)
    emit("kernel_limb_matmul_kom3_outacc", us, f"gflops={flops/us/1e3:.3f}")
    emit("kernel_limb_matmul_scratch_speedup",
         us / matmul_us["limb_True"], "x_vs_output_accum")

    # legacy single-image shim: its concrete taps are baked into the kernel
    # (§7), not traced into SMEM as its old outer jit forced.
    img = jnp.asarray(rng.integers(0, 256, (256, 256)), jnp.int32)
    kern = jnp.asarray(gaussian_kernel_3x3())
    for meth in ("exact", "refmlm", "mitchell"):
        us = time_fn(lambda i, k: gaussian_filter(i, k, method=meth), img, kern,
                     iters=3)
        emit(f"kernel_gauss_{meth}", us, f"mpix_s={256*256/us:.2f}")

    # filter-bank pipeline: filters x batch sizes on the autotuned grid,
    # with the fold-vs-serial-batch §8 before/after.
    _bank_scaling(rng, tag="bank_")

    # execution-mode rows (§9): local vs sharded vs streamed at n=32.
    _dist_variants(rng, tag="dist_")

    # the §7 dataflow pairs on the 5x5 Gaussian: direct (k*k taps) vs
    # separable (k+k taps), two-pass vs fused, and the default call.
    _bank_variants(_img_batch(rng, 4), tag="bank_")


def smoke(threshold: float = 1.0) -> int:
    """Reduced-size perf regression guard (scripts/check.sh).

    Fails when n=8 batched throughput (mpix/s) falls below n=1 for any
    guarded bank filter -- the §8 batch-scaling guarantee. The generous
    1.0x threshold only catches batching *losing*, not noise."""
    rng = np.random.default_rng(0)
    rc = 0
    mpix = _bank_scaling(rng, tag="smoke_", h=64, w=64,
                         filters=SCALING_GUARD_FILTERS)
    for filt in SCALING_GUARD_FILTERS:
        scaling = mpix[filt][8] / mpix[filt][1]
        print(f"# smoke: {filt} n8 scales {scaling:.2f}x vs n1 "
              f"(threshold {threshold}x)")
        if scaling < threshold:
            print(f"# FAIL: batching regresses {filt} throughput "
                  f"(n8 {mpix[filt][8]:.2f} < n1 {mpix[filt][1]:.2f} mpix/s)")
            rc = 1
    return rc


def smoke_dist(threshold: float = 1.0) -> int:
    """Multi-device perf + identity guard (scripts/check.sh, DESIGN.md §9).

    Requires >= 2 visible devices (check.sh starts the process with
    XLA_FLAGS=--xla_force_host_platform_device_count=8). Fails when
    (a) sharded or streamed output differs from local anywhere, or
    (b) sharded n=32 throughput falls below local n=32 for any guarded
    filter. The generous 1.0x threshold only catches scale-out *losing*."""
    from repro import distribute
    if distribute.device_count() < 2:
        print("# FAIL: --smoke-dist needs >= 2 devices; start with "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return 1
    rng = np.random.default_rng(0)
    rc = 0
    for filt in DIST_GUARD_FILTERS:
        out = _dist_variants(rng, tag="smoke_dist_", n=32, h=64, w=64,
                             filt=filt)
        for mode in ("sharded", "streamed"):
            if not out[mode]["identical"]:
                print(f"# FAIL: {mode} {filt} output is not bit-identical "
                      "to local")
                rc = 1
        scaling = out["sharded"]["mpix_s"] / out["local"]["mpix_s"]
        print(f"# smoke-dist: {filt} sharded runs {scaling:.2f}x local "
              f"mpix/s at n=32 (threshold {threshold}x)")
        if scaling < threshold:
            print(f"# FAIL: sharding regresses {filt} throughput "
                  f"(sharded {out['sharded']['mpix_s']:.2f} < local "
                  f"{out['local']['mpix_s']:.2f} mpix/s)")
            rc = 1
    return rc


def smoke_tune() -> int:
    """Plan-tuning guard (scripts/check.sh --smoke-tune, DESIGN.md §11).

    For each --quick sweep shape: (a) time every plan candidate once
    (exhaustive, prune=False), (b) replay the recorded timings through the
    pruned sweep and fail if pruning changed the winner, timed as many
    candidates as the exhaustive pass, or skipped nothing -- the roofline
    loop may only save time, never flip the winner. Takes a few minutes:
    the exhaustive pass times every candidate the real sweep prunes.
    """
    from repro.tuning.autotune import PLAN_QUICK, sweep_plan

    rc = 0
    for name, n, h, w in PLAN_QUICK:
        print(f"# smoke-tune: exhaustive {name} n{n}x{h}x{w} plan sweep "
              "(every candidate timed once -- this is the slow part)")
        full, records = sweep_plan(name, n, h, w, iters=1, prune=False,
                                   verbose=False)
        timed = dict(records)

        replay, _ = sweep_plan(name, n, h, w, prune=True,
                               measure_fn=lambda p: timed[p], verbose=False)
        keys = ("dataflow", "block_rows", "block_cols", "batch_fold")
        print(f"# smoke-tune: {name} n{n}x{h}x{w} exhaustive winner "
              f"{full['dataflow']} "
              f"br={full['block_rows']} bc={full['block_cols']} "
              f"fold={full['batch_fold']} ({full['us_per_call']}us); pruned "
              f"replay swept {replay['swept']}/{replay['candidates']} "
              f"(pruned {replay['pruned']})")
        if any(replay[k] != full[k] for k in keys):
            print(f"# FAIL: pruning discarded the measured winner (replay "
                  f"picked {replay['dataflow']} "
                  f"br={replay['block_rows']} bc={replay['block_cols']} "
                  f"fold={replay['batch_fold']})")
            rc = 1
        if not (replay["pruned"] > 0
                and replay["swept"] < replay["candidates"]):
            print("# FAIL: pruned replay timed every candidate -- the "
                  "roofline loop is not pruning")
            rc = 1
    return rc


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        sys.exit(smoke())
    if "--smoke-dist" in sys.argv[1:]:
        sys.exit(smoke_dist())
    if "--smoke-tune" in sys.argv[1:]:
        sys.exit(smoke_tune())
    main()
    write_bench_json()
