"""Online image-filter serving demo (repro.serve, DESIGN.md §10): a
concurrent mixed-shape load generator against the shape-bucketed
micro-batching server.

    PYTHONPATH=src python examples/serve_images.py \
        [--clients 4] [--requests 16] [--max-batch 8] [--max-delay-ms 2] \
        [--exec local|sharded|streamed] [--devices N] [--seed 0] \\
        [--infer] [--trace out.jsonl]

Each client thread plays a user stream: a random mix of image shapes and
bank filters, submitted as fast as the admission gate allows. Concurrent
requests that share a bucket -- same (H, W), filter, multiplier, exec
mode -- coalesce into one batched `apply_filter` call on the REFMLM
datapath (the §8 batch fold), so throughput rises with load while every
response stays bit-identical to the single-image call (spot-checked at
the end). The run prints the request-latency percentiles, the
batch-occupancy histogram, and the flush-trigger mix.

``--trace out.jsonl`` turns on the §15 request tracing: every request's
span (submit -> admit -> enqueue -> flush -> dispatch -> fulfil) is
written through to the JSONL file, and the run ends by printing the
Perfetto quickstart -- convert with
`python -m repro.obs.snapshot out.jsonl --chrome out.chrome.json` and
open the Chrome trace at https://ui.perfetto.dev (one track per bucket,
queued + dispatch slices per request).

``--infer`` turns the run into the §14 mixed-workload scenario: the same
server additionally registers `InferWorkload` (the calibrated MLP head +
CNN classifier) and every client stream interleaves classification
requests among the filter traffic. Filter and infer buckets never
coalesce (the workload suffix keys them apart) but share admission,
batching and the executor; both output classes are spot-checked
bit-identical to their direct calls.
"""
import argparse
import os
import sys
import threading
import time


def _early_device_flag(argv):
    """--devices N must set XLA_FLAGS before JAX initializes below."""
    n = None
    for i, arg in enumerate(argv):
        if arg == "--devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif arg.startswith("--devices="):
            n = arg.split("=", 1)[1]
    if n is None or not n.isdigit():
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={int(n)} " + flags).strip()


_early_device_flag(sys.argv[1:])

import numpy as np                                                # noqa: E402

from repro.core.platform import enable_compile_cache              # noqa: E402
from repro.filters import apply_filter                            # noqa: E402
from repro.serve import ImageFilterServer, ServerConfig           # noqa: E402

#: the mixed-shape/mixed-filter request population
SHAPES = ((64, 64), (128, 128), (96, 128))
FILTERS = ("gaussian3", "gaussian5", "sobel_x", "sharpen3")
#: the --infer request population (model, multiplier method)
INFER_HW = (8, 8)
INFER_POINTS = (("mlp", "refmlm"), ("cnn", "refmlm"),
                ("cnn", "mitchell_ecc2"))


def build_infer_models(seed: int = 1):
    """Calibrated §14 models for the --infer mixed-workload scenario."""
    from repro.data.images import inference_batch
    from repro.infer import MODELS, calibrate, init_params
    x_cal = inference_batch(4, INFER_HW, seed=100)
    return {name: calibrate(g := build(INFER_HW),
                            init_params(g, seed=seed), x_cal)
            for name, build in MODELS.items()}


def client_stream(rng, n, infer=False):
    """Yield ('filter', img, target, method) / ('infer', ...) requests."""
    for _ in range(n):
        if infer and rng.random() < 0.4:
            model, method = INFER_POINTS[rng.integers(len(INFER_POINTS))]
            x = rng.random(INFER_HW, dtype=np.float32)
            yield "infer", x, model, method
        else:
            shape = SHAPES[rng.integers(len(SHAPES))]
            filt = FILTERS[rng.integers(len(FILTERS))]
            yield ("filter", rng.integers(0, 256, shape).astype(np.int32),
                   filt, "refmlm")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16,
                    help="requests per client")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--exec", default="local", dest="exec_mode",
                    choices=("local", "sharded", "streamed"))
    ap.add_argument("--devices", type=int, default=None,
                    help="host devices for --exec sharded (pre-JAX flag)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--infer", action="store_true",
                    help="mixed §14 scenario: interleave classification "
                         "requests (InferWorkload) with the filter traffic")
    ap.add_argument("--trace", default=None, metavar="OUT.jsonl",
                    help="write the §15 request trace (JSONL) here; "
                         "convert via python -m repro.obs.snapshot")
    args = ap.parse_args()
    enable_compile_cache()

    infer_models = build_infer_models() if args.infer else None
    workloads = None
    if infer_models is not None:
        from repro.infer import InferWorkload
        workloads = {"infer": InferWorkload(infer_models)}

    cfg = ServerConfig(max_batch=args.max_batch,
                       max_delay_ms=args.max_delay_ms,
                       max_pending=4 * args.clients * args.requests,
                       exec=args.exec_mode, workloads=workloads,
                       trace=args.trace)
    latencies, done = [], []
    lock = threading.Lock()

    def run_client(cid):
        rng = np.random.default_rng(args.seed + cid)
        pending = [(wl, img, target, method, time.perf_counter(),
                    srv.submit(img, target, method=method, workload=wl,
                               exec="local" if wl == "infer" else None))
                   for wl, img, target, method in
                   client_stream(rng, args.requests, infer=args.infer)]
        for wl, img, target, method, t0, fut in pending:
            out = fut.result(300)
            dt = (time.perf_counter() - t0) * 1e3
            with lock:
                latencies.append(dt)
                done.append((wl, img, target, method, out))

    total = args.clients * args.requests
    print(f"{args.clients} clients x {args.requests} requests "
          f"({len(SHAPES)} shapes x {len(FILTERS)} filters, "
          f"exec={args.exec_mode}) ...")
    with ImageFilterServer(cfg) as srv:
        batches = sorted({1 << k for k in range(args.max_batch.bit_length())})
        srv.warmup(SHAPES, FILTERS, batches=batches)
        if infer_models is not None:
            for model, method in INFER_POINTS:
                srv.warmup((INFER_HW,), (model,), methods=(method,),
                           execs=("local",), batches=batches,
                           workload="infer")
        t0 = time.perf_counter()
        threads = [threading.Thread(target=run_client, args=(c,))
                   for c in range(args.clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = srv.stats()

    mpix = sum(img.shape[0] * img.shape[1]
               for wl, img, *_ in done if wl == "filter") / wall / 1e6
    n_infer = sum(1 for wl, *_ in done if wl == "infer")
    p50, p95, p99 = np.percentile(latencies, [50, 95, 99])
    print(f"\nserved {stats['served']}/{total} requests in {wall*1e3:.0f} ms "
          f"({mpix:.2f} mpix/s filtered"
          + (f", {n_infer} images classified)" if args.infer else ")"))
    print(f"latency p50/p95/p99: {p50:.1f} / {p95:.1f} / {p99:.1f} ms")
    print("occupancy histogram:",
          {n: c for n, c in sorted(stats['occupancy'].items())})
    print("flush triggers:", stats["flush_reasons"],
          "| warm hits/misses:",
          f"{stats['compile']['hits']}/{stats['compile']['misses']}")

    # bit-identity spot check: a served response is the direct call's bytes
    rng = np.random.default_rng(args.seed)
    checked = {"filter": 0, "infer": 0}
    for wl, img, target, method, out in (done[i] for i in
                                         rng.integers(0, len(done), size=8)):
        if wl == "filter":
            direct = np.asarray(apply_filter(img, target,
                                             exec=args.exec_mode))
        else:
            from repro.infer import forward
            direct = np.asarray(forward(infer_models[target], img[None],
                                        method))[0]
        assert (out == direct).all(), f"{wl}/{target} served != direct"
        checked[wl] += 1
    kinds = ", ".join(f"{n} {wl}" for wl, n in checked.items() if n)
    print(f"spot check ({kinds}): served outputs bit-identical to the "
          "direct call.")

    if args.trace:
        print(f"\ntrace: {stats['submitted']} request spans in "
              f"{args.trace}. Inspect with\n"
              f"  PYTHONPATH=src python -m repro.obs.snapshot {args.trace} "
              f"--chrome {args.trace}.chrome.json\n"
              "then open the .chrome.json at https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
