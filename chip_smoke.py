"""Chip smoke test: drive the served REFMLM filter path once on a TPU.

    python chip_smoke.py                # one chip: direct calls, serving,
                                        # inference
    python chip_smoke.py --four-chips   # four chips: sharded exec and the
                                        # executor pool, nothing else

Everything runs in this one process (a chip belongs to one process), at
deployment sizes, through the user entry points `repro.filters.apply_filter`
and `repro.serve.ImageFilterServer.submit`. Kernels are whatever the backend
autodetect picks -- the script asserts that it picked compiled Mosaic
kernels (`tpu_custom_call` in the lowered program), never the interpreter.

Each phase compares its outputs byte for byte with a plain reference
(`repro.filters.ref.apply_filter_ref`, the int8 inference oracle, the
reference matmuls) and fails on any mismatch. A server's `stats()` must end
clean -- healthy, nothing failed, shed, retried or degraded, and every
submitted request served -- so a failure that the serving layer's
fallbacks would swallow still fails the run.

The `setup-timing` lines are compile (first call) and warm (repeat call)
seconds of each phase: set-up timings, not benchmark numbers. The last
line of stdout is one JSON object naming the device. Where JAX finds no
TPU, the script exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SEED = 0
#: FVC2004 DB1 impression size (H, W), 8-bit, and the enrolment batch.
DB1 = (480, 640)
DIRECT_BATCH = 8
#: served traffic: (shape, filter, method, requests). Shapes are FVC2004
#: DB1 and DB2 impressions, 128x128 fingerprint patches (small enough to
#: batch-fold) and 1080p camera frames.
SERVE_MIX = (
    ((480, 640), "gaussian5", "refmlm", 4),
    ((480, 640), "sobel_x", "exact", 4),
    ((364, 328), "gaussian5", "exact", 4),
    ((364, 328), "sobel_x", "refmlm", 4),
    ((128, 128), "gaussian5", "refmlm", 8),
    ((128, 128), "sobel_x", "refmlm", 8),
    ((1080, 1920), "gaussian5", "refmlm", 2),
    ((1080, 1920), "sobel_x", "exact", 2),
)
SERVE_CLIENTS = 4
INFER_HW = (8, 8)
#: (M, K, N) of the approximate-matmul check (K <= 256 keeps the LNS
#: reference's f32 sums exact at nbits=8).
MATMUL_MKN = (64, 256, 128)
#: four-chip phases: one multispectral-scale scene, and the pool traffic.
SCENE = (8192, 8192)
POOL = ((0,), (1,), (2,), (3,))
POOL_REQUESTS = 32
POOL_CANDIDATES = tuple(
    (shape, filt, method)
    for shape in ((480, 640), (364, 328), (128, 128))
    for filt in ("gaussian5", "sobel_x", "gaussian3")
    for method in ("refmlm", "exact"))


class SmokeFailure(Exception):
    """A phase's result is wrong or its server reported a failure."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def same_bytes(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


def timed(fn):
    """(result, seconds) of fn() with its device work finished."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def setup_timing(phase: str, compile_s: float, warm_s: float) -> None:
    log(f"setup-timing {phase}: compile+first-run {compile_s:.3f} s, "
        f"warm {warm_s:.3f} s (set-up timings, not benchmark numbers)")


def assert_autodetect() -> None:
    """Backend autodetect resolved to compiled kernels, not the
    interpreter: every pass below runs through Mosaic."""
    from repro.core.platform import default_interpret
    check(not default_interpret(), "backend autodetect chose the interpreter")


def assert_compiled(fn, *args, what: str) -> None:
    """The lowered program holds a Mosaic kernel: autodetect compiled."""
    import jax
    text = jax.jit(fn).lower(*args).as_text()
    check("tpu_custom_call" in text, f"{what}: no tpu_custom_call in the "
          "lowered program -- the kernel did not compile for the chip")


def ref_filter():
    """The jitted plain reference of `apply_filter`."""
    import jax
    from repro.filters.ref import apply_filter_ref
    return jax.jit(apply_filter_ref,
                   static_argnames=("filt", "method", "nbits", "separable"))


def check_server(srv, what: str) -> dict:
    """Fail on any failure the serving layer swallowed or fell back from."""
    st = srv.stats()
    log(f"{what} stats: submitted={st['submitted']} served={st['served']} "
        f"batches={st['batches']} occupancy={st['occupancy']} "
        f"compile hits/misses={st['compile']['hits']}/"
        f"{st['compile']['misses']}")
    check(st["healthy"], f"{what}: server unhealthy ({st['last_error']})")
    for field in ("failed", "errors", "shed", "shed_overload", "retries",
                  "isolated"):
        check(st[field] == 0, f"{what}: stats()[{field!r}] = {st[field]}")
    check(not st["degraded"], f"{what}: degraded buckets {st['degraded']}")
    check(st["served"] == st["submitted"],
          f"{what}: served {st['served']} of {st['submitted']}")
    return st


# ------------------------------------------------------------------ one chip

def phase_direct(ref) -> None:
    """apply_filter on an enrolment batch, every dataflow and method."""
    import jax.numpy as jnp
    import numpy as np
    from repro.data.images import fingerprint
    from repro.filters import apply_filter

    imgs = jnp.asarray(np.stack([fingerprint(DB1, seed=SEED + i)
                                 for i in range(DIRECT_BATCH)]))
    assert_compiled(lambda v: apply_filter(v, "gaussian5"), imgs,
                    what="apply_filter(gaussian5)")
    dataflows = {"fused": {"fused": True}, "two_pass": {"fused": False},
                 "direct": {"separable": False}, "default": {}}
    cases = [("gaussian5", "fused"), ("gaussian5", "two_pass"),
             ("gaussian5", "direct"), ("gaussian3", "default"),
             ("sobel_x", "default")]
    first_s = warm_s = 0.0
    for filt, df in cases:
        for method in ("refmlm", "exact", "mitchell"):
            kw = dataflows[df]
            call = lambda: apply_filter(imgs, filt, method=method, **kw)  # noqa: E731
            out, t_first = timed(call)
            out2, t_warm = timed(call)
            want = ref(imgs, filt, method=method, separable=df != "direct")
            check(same_bytes(out, want) and same_bytes(out2, want),
                  f"direct {filt}/{df}/{method}: output != reference")
            first_s += t_first
            warm_s += t_warm
            log(f"direct {filt}/{df}/{method} {tuple(imgs.shape)}: "
                "byte-equal to reference")
    setup_timing("direct", first_s, warm_s)


def infer_models():
    from repro.data.images import inference_batch
    from repro.infer import MODELS, calibrate, init_params
    graph = MODELS["cnn"](INFER_HW)
    return {"cnn": calibrate(graph, init_params(graph, seed=1),
                             inference_batch(4, INFER_HW, seed=100))}


def assert_served_plans() -> None:
    """The executor's plan for every served bucket takes the recursion
    (Mosaic cannot lower the KCM gather) and lowers to a Mosaic kernel."""
    import numpy as np
    from repro.filters import apply_filter, resolve_filter_plan

    for (h, w), filt, method, _ in SERVE_MIX:
        plan = resolve_filter_plan(filt, 1, h, w, method=method)
        check(plan.mult_impl == "recurse",
              f"served plan for {filt}/{h}x{w} pins {plan.mult_impl!r}")
    (h, w), filt, method, _ = SERVE_MIX[0]
    plan = resolve_filter_plan(filt, 1, h, w, method=method)
    assert_compiled(
        lambda v: apply_filter(v, filt, method=method,
                               separable=plan.dataflow != "direct",
                               fused=plan.dataflow == "fused",
                               mult_impl=plan.mult_impl,
                               block_rows=plan.block_rows,
                               block_cols=plan.block_cols,
                               batch_fold=plan.batch_fold),
        np.zeros((1, h, w), np.uint8), what="served plan")


def phase_serving(srv, ref) -> None:
    """Concurrent mixed-shape filter traffic through submit()."""
    import numpy as np
    from repro.data.images import fingerprint

    assert_served_plans()
    t0 = time.perf_counter()
    for shape, filt, method, _ in SERVE_MIX:
        srv.warmup([shape], [filt], methods=[method], batches=(1, 2, 4, 8))
    compile_s = time.perf_counter() - t0

    rng = np.random.default_rng(SEED)
    reqs = []
    for shape, filt, method, count in SERVE_MIX:
        for i in range(count):
            img = fingerprint(shape, seed=int(rng.integers(1 << 30)))
            reqs.append((img, filt, method))
    order = rng.permutation(len(reqs))
    futures: dict[int, object] = {}
    errors: list[BaseException] = []

    def client(cid: int) -> None:
        try:
            for k in order[cid::SERVE_CLIENTS]:
                img, filt, method = reqs[k]
                futures[int(k)] = srv.submit(img, filt, method=method)
        except BaseException as err:                      # noqa: BLE001
            errors.append(err)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(SERVE_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    check(not errors, f"submit raised: {errors[:1]}")
    outs = {k: f.result(600) for k, f in futures.items()}
    warm_s = time.perf_counter() - t0
    for k, (img, filt, method) in enumerate(reqs):
        want = ref(img[None], filt, method=method)[0]
        check(same_bytes(outs[k], want),
              f"served {filt}/{method} {img.shape}: output != reference")
    log(f"serving: {len(reqs)} requests over {len(SERVE_MIX)} buckets from "
        f"{SERVE_CLIENTS} clients, every result byte-equal to reference")
    setup_timing("serving", compile_s, warm_s)


def phase_inference(srv, models) -> None:
    """Quantized inference through the same server, plus the Pallas
    matmul kernels that approx_matmul's impl='auto' picks on a TPU."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import approx_matmul
    from repro.data.images import inference_batch
    from repro.infer import forward

    x = inference_batch(1, INFER_HW, seed=SEED + 7)[0]
    t0 = time.perf_counter()
    srv.warmup([INFER_HW], ["cnn"], methods=["refmlm", "int8"],
               execs=["local"], workload="infer")
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    futs = {m: srv.submit(x, "cnn", method=m, workload="infer")
            for m in ("refmlm", "int8")}
    logits = {m: np.asarray(f.result(600)) for m, f in futs.items()}
    warm_s = time.perf_counter() - t0
    check(same_bytes(logits["refmlm"], logits["int8"]),
          "served refmlm logits != int8 oracle logits")
    direct = np.asarray(forward(models["cnn"], x[None], "int8"))[0]
    check(same_bytes(logits["int8"], direct),
          "served int8 logits != direct forward")
    log("inference: cnn refmlm logits byte-equal to the int8 oracle")

    m, k, n = MATMUL_MKN
    rng = np.random.default_rng(SEED)
    a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    for method in ("mitchell", "karatsuba_int16"):
        check(approx_matmul._resolve_impl("auto", method) == "pallas",
              f"matmul impl='auto' did not pick pallas for {method}")
        assert_compiled(lambda p, q: approx_matmul.matmul(
            p, q, method, impl="auto"), a, b, what=f"matmul {method}")
        (got, t_first) = timed(lambda: approx_matmul.matmul(
            a, b, method, impl="auto"))
        want = approx_matmul.matmul(a, b, method, impl="reference")
        check(same_bytes(got, want),
              f"matmul {method}: pallas != reference")
        compile_s += t_first
        log(f"matmul {method} {MATMUL_MKN}: pallas byte-equal to reference")
    setup_timing("inference", compile_s, warm_s)


def run_one_chip() -> None:
    from repro.infer import InferWorkload
    from repro.serve import ImageFilterServer, ServerConfig

    assert_autodetect()
    ref = ref_filter()
    phase_direct(ref)
    models = infer_models()
    cfg = ServerConfig(workloads={"infer": InferWorkload(models)})
    with ImageFilterServer(cfg) as srv:
        phase_serving(srv, ref)
        phase_inference(srv, models)
        check_server(srv, "server")


# ---------------------------------------------------------------- four chips

def phase_sharded() -> None:
    """One large scene row-sharded over 4 chips vs the local path."""
    import jax.numpy as jnp
    import numpy as np
    from repro.filters import apply_filter

    scene = jnp.asarray(np.random.default_rng(SEED).integers(
        0, 256, SCENE, dtype=np.uint8))
    sharded, t_first = timed(lambda: apply_filter(
        scene, "gaussian5", method="refmlm", exec="sharded", devices=4))
    _, t_warm = timed(lambda: apply_filter(
        scene, "gaussian5", method="refmlm", exec="sharded", devices=4))
    spread = sharded.sharding.device_set
    check(len(spread) == 4, f"sharded output spans {len(spread)} devices")
    local, t_local = timed(lambda: apply_filter(scene, "gaussian5",
                                                method="refmlm"))
    check(same_bytes(sharded, local), "sharded output != local output")
    log(f"sharded gaussian5/refmlm {SCENE} over {len(spread)} devices "
        f"(mesh 1x4): byte-equal to exec='local'")
    setup_timing("sharded", t_first, t_warm)
    setup_timing("sharded-local-reference", t_local, 0.0)


def pool_buckets() -> list:
    """One bucket per pool member, chosen by the pool's own routing."""
    from repro.serve.pool import rendezvous_score
    from repro.serve.request import bucket_key
    members = [f"m{i}" for i in range(len(POOL))]
    chosen: dict[str, tuple] = {}
    for shape, filt, method in POOL_CANDIDATES:
        key = bucket_key(filt, method, "auto", "local", 8, *shape)
        owner = max(members, key=lambda name: rendezvous_score(name, key))
        chosen.setdefault(owner, (shape, filt, method))
    check(len(chosen) == len(members),
          f"no candidate bucket routes to {set(members) - set(chosen)}")
    return [chosen[name] for name in members]


def phase_pool(ref) -> None:
    """A pool of one-chip members: each serves its buckets on its chip."""
    import jax
    from repro.data.images import fingerprint
    from repro.serve import ImageFilterServer, ServerConfig

    devices = jax.devices()[:len(POOL)]
    before = [d.memory_stats() or {} for d in devices]
    buckets = pool_buckets()
    per = POOL_REQUESTS // len(buckets)
    reqs = [(fingerprint(shape, seed=SEED + 100 * b + i), filt, method)
            for b, (shape, filt, method) in enumerate(buckets)
            for i in range(per)]
    # a wide flush window so each bucket's burst rides one batch of 8
    cfg = ServerConfig(pool=POOL, max_delay_ms=200.0)
    t0 = time.perf_counter()
    with ImageFilterServer(cfg) as srv:
        futs = [srv.submit(img, filt, method=method)
                for img, filt, method in reqs]
        outs = [f.result(600) for f in futs]
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        futs = [srv.submit(img, filt, method=method)
                for img, filt, method in reqs]
        again = [f.result(600) for f in futs]
        warm_s = time.perf_counter() - t0
        st = check_server(srv, "pool server")
    for (img, filt, method), out, out2 in zip(reqs, outs, again):
        want = ref(img[None], filt, method=method)[0]
        check(same_bytes(out, want) and same_bytes(out2, want),
              f"pool {filt}/{method} {img.shape}: output != reference")
    for name, m in st["pool"]["members"].items():
        check(m["dispatches"] > 0, f"pool member {name} served nothing")
    after = [d.memory_stats() or {} for d in devices]
    for d, b, a in zip(devices, before, after):
        moved = (a.get("num_allocs", 0) > b.get("num_allocs", 0)
                 or a.get("peak_bytes_in_use", 0) > b.get("peak_bytes_in_use", 0))
        check(moved, f"no work on device {d.id}: memory_stats {b} -> {a}")
    log(f"pool: {2 * len(reqs)} requests over {len(POOL)} one-chip members, "
        "every member served on its own device, byte-equal to reference")
    setup_timing("pool", first_s, warm_s)


def run_four_chips() -> None:
    assert_autodetect()
    phase_pool(ref_filter())
    phase_sharded()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded and pool phases on 4 chips")
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default backend is "
              f"{backend!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.core.platform import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"device: {devices[0].device_kind} x{len(devices)}")
    t0 = time.perf_counter()
    try:
        run_four_chips() if args.four_chips else run_one_chip()
    except SmokeFailure as err:
        print(f"chip_smoke FAILED: {err}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
