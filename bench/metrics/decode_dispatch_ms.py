"""Median host time of the executor's decode dispatch, from the program's
span `executor.decode.dispatch`: the batch's inputs built and put on the
device, the decode step traced and lowered or fetched, and enqueued. What
follows it until the logits are on the host (`executor.decode.fetch`:
waiting for the device, the copy) is not in it."""
from bench import program_spans


def read(r):
    return program_spans.median_ms(r, "executor.decode.dispatch")
