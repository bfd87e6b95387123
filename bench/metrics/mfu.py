"""Model FLOPs of every prompt and output token served in the window (the
benchmark's own count, bench/work.py) over the window's length times the
chip's bf16 peak."""
from bench import work


def read(r):
    if r.peaks is None or not r.steps:
        return None
    flops = 0
    for s in r.steps:
        flops += sum(work.prefill_flops(r.shape, n) for n in s.prefill_lens)
        flops += sum(work.decode_flops(r.shape, c) for c in s.decode_ctx)
    return 100.0 * flops / (r.window_s * r.peaks.flops_bf16 * r.chips)
