"""Share of the roofline reached by the filters.conv Pallas kernels: the
least time of the filtering work that the window's requests asked for, over
the summed device time of the operations that `kernels/conv.json`
patterns match. Silent when no operation matched."""
import kernelwork


def read(run):
    return kernelwork.roofline_pct(run, "conv")
