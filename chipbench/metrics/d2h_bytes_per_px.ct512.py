"""Bytes copied device -> host per real pixel served in the window, from
the server's `transfer` counters (`serve_d2h_bytes_total` over
`serve_pixels_total`, pad images in the bytes and not in the pixels). A
program that does not count them gives nothing to read."""


def read(run):
    after, before = run.stats.get("transfer"), run.stats_before.get("transfer")
    if not after or not before or "d2h_bytes" not in after:
        return None
    moved = (sum(after["d2h_bytes"].values())
             - sum(before.get("d2h_bytes", {}).values()))
    pixels = sum(after["pixels"].values()) - sum(before["pixels"].values())
    return moved / pixels if pixels else None
