"""Median host time of one whole prompt's prefill, from the program's span
`executor.prefill`: the prefill program, the scatter of its KV into the
pool, and the last position's logits to the host."""
from bench import program_spans


def read(r):
    return program_spans.median_ms(r, "executor.prefill")
