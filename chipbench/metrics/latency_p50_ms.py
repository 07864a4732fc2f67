"""Median latency, due instant to answer, over every request of the window."""
from harness import nearest_rank


def read(run):
    lat = run.latencies_ms()
    return nearest_rank(lat, 50) if lat else None
