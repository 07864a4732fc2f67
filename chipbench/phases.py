"""The serve worker's phase spans in a traced run's obs events.

With tracing on, the program records each leaf phase of its one worker
thread as a 'span' event (`ts` start, `end`, `span` the phase, and the
`batch` id of the batch it served). The leaves tile the worker's time:

    idle -> collect -> prep -> upload -> launch -> device_wait -> fetch
    -> fulfil

A program that records no such spans gives the readers nothing to read,
and they return None.
"""
from harness import nearest_rank

#: the worker's leaf phases (the program's `repro.obs.trace.PHASES`).
LEAVES = ("idle", "collect", "prep", "upload", "launch", "device_wait",
          "fetch", "fulfil")


def window_leaves(run) -> list[dict]:
    """The leaf spans that start between the window's opening and the
    last answer."""
    return [ev for ev in run.events or ()
            if ev.get("event") == "span" and ev.get("span") in LEAVES
            and run.t_start <= ev["ts"] <= run.t_last]


def per_batch_ms(run, phase: str) -> float | None:
    """Median over the batches dispatched in the window of the time each
    spent in `phase` (ms)."""
    per_batch: dict[int, float] = {}
    for ev in window_leaves(run):
        if ev["span"] == phase and ev.get("batch") is not None:
            per_batch[ev["batch"]] = (per_batch.get(ev["batch"], 0.0)
                                      + ev["end"] - ev["ts"])
    if not per_batch:
        return None
    return nearest_rank([s * 1e3 for s in per_batch.values()], 50)


def seconds_by_phase(run) -> dict[str, float]:
    """Summed seconds of each leaf phase in the window."""
    out: dict[str, float] = {}
    for ev in window_leaves(run):
        out[ev["span"]] = out.get(ev["span"], 0.0) + ev["end"] - ev["ts"]
    return out
