"""Median per batch of the serve executor's 'device_wait' phase span: the
device run of the batch as the host sees it, from the dispatch's return to
the output being ready, over the batches dispatched in the window (ms)."""
import phases


def read(run):
    return phases.per_batch_ms(run, "device_wait")
