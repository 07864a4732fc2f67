"""Pixels of every request sent in the window, over the time from the
window's opening to the last answer (the drain counts, so a stall shows)."""


def read(run):
    reqs = run.requests
    if not reqs:
        return None
    pixels = sum(run.kinds[r.kind].pixels for r in reqs if r.ok)
    return pixels / max(run.t_last - run.t_start, 1e-9) / 1e6
