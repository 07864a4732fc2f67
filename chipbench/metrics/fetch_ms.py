"""Median per batch of the serve executor's 'fetch' phase span: the
device -> host copy of the output and its split per request, over the
batches dispatched in the window (ms)."""
import phases


def read(run):
    return phases.per_batch_ms(run, "fetch")
