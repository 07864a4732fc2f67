"""The work a filter needs, whatever implements it, and its least time.

A filter over one pixel reads it and writes one in the container of the
configuration's bit depth (1 byte up to 8 bits, else 2), and takes kh*kw
multiplies and as many adds: 2*kh*kw operations. Pad images,
halos, intermediate passes and the width of the implementation's integer
types are not work the user asked for, so they are not counted. The least
time of a piece of work is the larger of its bytes over the HBM peak and
its operations over the int8 peak (the highest the chip publishes, so a
share of the roofline is never overstated).
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def load_peaks(path: Path = PEAKS_FILE) -> dict:
    """device_kind -> published peaks of one chip."""
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def filter_work(pixels: int, kh: int, kw: int,
                bytes_per_px: int = 1) -> tuple[float, float]:
    """(operations, bytes) of filtering `pixels` pixels of `bytes_per_px`
    bytes each with a kh x kw filter."""
    return 2.0 * kh * kw * pixels, 2.0 * bytes_per_px * pixels


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(seconds, bound) -- the roofline's least time and which bound sets it."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")


def roofline_pct(run, group: str) -> float | None:
    """Least time of the filtering work of a traced run's answered requests
    over the device time of kernel group `group`, in percent; None when no
    operation of the group was found."""
    if run.device is None or not run.device.kernel_s.get(group):
        return None
    least = 0.0
    for r in run.requests:
        if r.ok:
            kind = run.kinds[r.kind]
            ops, nbytes = filter_work(kind.pixels, *kind.ksize,
                                      kind.bytes_per_px)
            least += least_time(ops, nbytes, run.peak)[0]
    return 100.0 * least / run.device.kernel_s[group]
