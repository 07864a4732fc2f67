"""conv_roofline in the CT series cell: the 16-bit row pass and 32-bit
column pass of the fused kernel against the roofline of the work asked for
(2 bytes in and out a pixel, 2*kh*kw operations)."""
import kernelwork


def read(run):
    return kernelwork.roofline_pct(run, "conv")
