"""Median per batch of the serve executor's 'upload' phase span: the
host -> device copy of the stacked batch, which a traced run waits for so
that it is split from the device run, over the batches dispatched in the
window (ms)."""
import phases


def read(run):
    return phases.per_batch_ms(run, "upload")
