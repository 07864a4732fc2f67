"""Median dispatch -> fulfil of the window's requests, from the obs spans:
host stack, pad and cast, the device run, and the result's fetch."""
from harness import nearest_rank


def read(run):
    times = [(s["fulfil"] - s["dispatch"]) * 1e3 for s in run.spans().values()
             if "fulfil" in s and "dispatch" in s]
    return nearest_rank(times, 50) if times else None
