"""2x2 base products per output pixel of the REFMLM kernels that served
the window, from the program's `conv_tap_leaves` gauge (stats()): for each
answered request, the baked build of its filter's taps at the cell's
multiplier width; where a filter was built at more than one column-pass
width, the widest, which a 16-bit container takes. Averaged over the
answered pixels; nothing to read where no build matches."""
import harness


def _labels(series: str) -> dict:
    return dict(part.split("=", 1) for part in series.split(",") if part)


def _leaves(gauge: dict, method: str, nbits: int, spec: dict):
    taps = (" ".join(("_".join(map(str, spec["row"])),
                      "/".join(map(str, spec["col"])))))
    best = None
    for series, value in gauge.items():
        lab = _labels(series)
        widths = lab.get("nbits", "").split("/")
        if (lab.get("coef") == "baked" and lab.get("method") == method
                and lab.get("taps") == taps and widths[0] == str(nbits)):
            width = int(widths[-1])
            if best is None or width > best[0]:
                best = (width, value)
    return None if best is None else best[1]


def read(run):
    gauge = run.stats.get("conv_tap_leaves")
    if not gauge:
        return None
    mult = harness.multiplier(run.cell)
    leaves = pixels = 0
    for r in run.requests:
        if not r.ok:
            continue
        kind = run.kinds[r.kind]
        per_px = _leaves(gauge, mult["method"], mult["nbits"], kind.spec)
        if per_px is None:
            return None
        leaves += per_px * kind.pixels
        pixels += kind.pixels
    return leaves / pixels if pixels else None
