"""Median per batch of the serve executor's 'prep' phase span: the host
side of the batch (stack, cast to the device's integer width, zero-pad to
the traced batch size), over the batches dispatched in the window (ms)."""
import phases


def read(run):
    return phases.per_batch_ms(run, "prep")
