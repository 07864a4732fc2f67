"""Bytes copied host -> device per real pixel served in the window, from
the server's `transfer` counters (`serve_h2d_bytes_total` over
`serve_pixels_total`, pad images in the bytes and not in the pixels)."""


def read(run):
    after, before = run.stats.get("transfer"), run.stats_before.get("transfer")
    if not after or not before:
        return None
    moved = sum(after["h2d_bytes"].values()) - sum(before["h2d_bytes"].values())
    pixels = sum(after["pixels"].values()) - sum(before["pixels"].values())
    return moved / pixels if pixels else None
