"""The busiest pool member's dispatches in the window over the members'
mean, from the server's pool counters (1 is even; 4 is one member of four
doing everything)."""


def read(run):
    pool = run.stats.get("pool")
    if not pool:
        return None
    before = run.stats_before["pool"]["members"]
    counts = [m["dispatches"] - before[name]["dispatches"]
              for name, m in pool["members"].items()]
    mean = sum(counts) / len(counts)
    return max(counts) / mean if mean else None
