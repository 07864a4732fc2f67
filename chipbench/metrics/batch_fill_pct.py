"""Real images over traced batch slots in the window's batches, from the
server's occupancy counter and its power-of-two rounding."""


def read(run):
    before = run.stats_before["occupancy"]
    real = slots = 0
    for n, count in run.stats["occupancy"].items():
        count -= before.get(n, 0)
        real += n * count
        slots += (1 << max(0, n - 1).bit_length()) * count
    return 100.0 * real / slots if slots else None
