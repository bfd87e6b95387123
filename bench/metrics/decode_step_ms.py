"""Median host time of `executor.step` calls that decode and prefill
nothing: from the call until the logits are host arrays."""
import statistics


def read(r):
    t = [s.t1 - s.t0 for s in r.steps if s.decode_rows and not s.prefill_lens]
    return statistics.median(t) * 1e3 if t else None
