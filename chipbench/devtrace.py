"""Reduction of a profiler trace to the benchmark's device numbers.

`load_xplane` reads the `.xplane.pb` that `jax.profiler` writes and keeps
two things: each device's operations (name, start, end) from its op line,
and the host threads' events. `reduce_trace` turns them into:

- busy time: the union of a device's operation intervals, averaged over the
  devices used, and the idle share of the traced window;
- kernel time: the summed device time of the operations whose names match
  a group's patterns (`kernels/<group>.json`), and the share of operation time that
  no pattern matched;
- a breakdown: the device operations that took most time, and the longest
  idle gaps, each labelled by the host event that overlaps it most.

Times are nanoseconds on the trace's own clock; the reduction returns
seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from pathlib import Path

KERNELS_DIR = Path(__file__).resolve().parent / "kernels"

#: the line of a device plane that holds one event per operation.
OPS_LINE = "XLA Ops"

#: host events of the benchmark's own load generator (`TraceAnnotation`s).
OWN_PREFIX = "chipbench."

#: breakdown lists keep this many entries.
TOP = 10

Event = tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Event]]   # device plane name -> its operations
    host: list[Event]                 # every host thread's events


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                     # mean over devices
    idle_share: float
    kernel_s: dict[str, float]        # pattern group -> summed op seconds
    op_s: float                       # all operations' summed seconds
    unmatched_share: float
    device_ops: list[list]            # [[name, seconds], ...]
    idle_gaps: list[list]             # [[label, seconds], ...]


def load_kernel_patterns(root: Path = KERNELS_DIR) -> dict[str, list[str]]:
    """Kernel group -> regular expressions over device operation names, one
    file per group: `kernels/<group>.json` holds the group's list."""
    groups = {}
    for path in sorted(root.glob("*.json")):
        with open(path, encoding="utf-8") as f:
            groups[path.stem] = json.load(f)
    return groups


def find_xplane(log_dir: str) -> str:
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _events(line) -> list[Event]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def load_xplane(path: str) -> Trace:
    """Device op lines and host thread events of one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            if ops:
                devices[plane.name] = _events(ops[0])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return Trace(devices, host)


def event_span(trace: Trace, name: str) -> tuple[int, int]:
    """(start, end) of the longest host event called `name`."""
    spans = [(s, e) for n, s, e in trace.host if n == name]
    if not spans:
        raise ValueError(f"the trace has no host event {name!r}")
    return max(spans, key=lambda se: se[1] - se[0])


def merge(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def op_family(name: str) -> str:
    """An operation's instruction name without its instance number, with
    its result type: `%fusion.12 = s32[8,480]{...} ...` -> `fusion
    s32[8,480]`."""
    m = re.match(r"%?([^\s=]+?)(?:\.\d+)? = (\S+?)(?:\{|\s|$)", name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def _label(gap: tuple[int, int], host: list[Event], longest: int) -> str:
    """The host event overlapping `gap` most, leaving out events that span
    more than `longest` (whole-thread events say nothing)."""
    best, best_key = "no host event", (0, 0)
    for name, s, e in host:
        if e - s > longest:
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        # the program's own events before the load generator's
        key = (0 if name.startswith(OWN_PREFIX) else 1, overlap)
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_trace(trace: Trace, patterns: dict[str, list[str]], *,
                 window: tuple[int, int] | None = None) -> Reduction:
    """Busy, idle, kernel and breakdown numbers of a trace over `window`
    (default: the span of the host events, i.e. the traced session)."""
    if not trace.devices:
        raise ValueError("the trace holds no device operations")
    if window is None:
        stamps = [t for _, s, e in trace.host for t in (s, e)]
        stamps += [t for ops in trace.devices.values()
                   for _, s, e in ops for t in (s, e)]
        window = (min(stamps), max(stamps))
    w0, w1 = window
    span = max(w1 - w0, 1)
    compiled = {g: [re.compile(p) for p in ps] for g, ps in patterns.items()}
    kernel_ns = {g: 0 for g in compiled}
    op_ns = matched_ns = 0
    families: dict[str, int] = {}
    busy_ns = []
    gaps: list[tuple[int, int]] = []
    for ops in trace.devices.values():
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in ops
                   if e > w0 and s < w1]
        busy = merge(clipped)
        busy_ns.append(sum(e - s for s, e in busy))
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, s, e in ops:
            dur = e - s
            op_ns += dur
            fam = op_family(name)
            families[fam] = families.get(fam, 0) + dur
            hit = False
            for group, regs in compiled.items():
                if any(r.search(name) for r in regs):
                    kernel_ns[group] += dur
                    hit = True
            matched_ns += dur if hit else 0
    busy_mean = sum(busy_ns) / len(busy_ns)
    top_ops = sorted(families.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return Reduction(
        window_s=span / 1e9,
        busy_s=busy_mean / 1e9,
        idle_share=1.0 - busy_mean / span,
        kernel_s={g: ns / 1e9 for g, ns in kernel_ns.items()},
        op_s=op_ns / 1e9,
        unmatched_share=(1.0 - matched_ns / op_ns) if op_ns else 0.0,
        device_ops=[[name, ns / 1e9] for name, ns in top_ops],
        idle_gaps=[[_label(g, trace.host, span // 2), (g[1] - g[0]) / 1e9]
                   for g in top_gaps])

