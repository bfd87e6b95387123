"""Mean share of the decode batch's rows that carry a live sequence, over
the window's engine calls that decode, read from the batch handed to the
executor."""


def read(r):
    dec = [s for s in r.steps if s.decode_rows]
    if not dec:
        return None
    return 100.0 * sum(s.decode_rows / s.max_rows for s in dec) / len(dec)
