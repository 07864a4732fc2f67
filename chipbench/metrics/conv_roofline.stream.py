"""conv_roofline in the camera stream's cell, where it moves latency."""
import kernelwork


def read(run):
    return kernelwork.roofline_pct(run, "conv")
