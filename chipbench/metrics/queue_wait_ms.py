"""Median enqueue -> flush of the window's requests, from the obs spans."""
from harness import nearest_rank


def read(run):
    waits = [(s["flush"] - s["enqueue"]) * 1e3 for s in run.spans().values()
             if "flush" in s and "enqueue" in s]
    return nearest_rank(waits, 50) if waits else None
