"""The paged-attention kernel's least time for the work its live rows need
(q and output bytes, and K and V of each row's real context, at the pool's
dtype), over the summed device time of the kernel's events in the trace."""
from bench import work

KERNEL = "paged_attention"


def read(r):
    if r.trace is None or r.peaks is None:
        return None
    spent = r.trace.kernel_seconds(KERNEL)
    ctx = [s.decode_ctx for s in r.steps if s.decode_ctx]
    if spent <= 0 or not ctx:
        return None
    flops = nbytes = 0
    for c in ctx:
        f, b = work.paged_attention_work(r.shape, c, r.kv_bytes, r.q_bytes)
        flops, nbytes = flops + f, nbytes + b
    least, _ = work.least_time(flops, nbytes, r.peaks.flops_bf16,
                               r.peaks.hbm_bytes)
    return 100.0 * least / spent
