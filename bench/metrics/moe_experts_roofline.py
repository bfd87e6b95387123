"""The routed experts' grouped matmul in the decode step: the least time
for the work the live rows routed to them need (bench/families/qwen3_moe.py
`expert_work`: 6 d f FLOPs a routed row; the gate, up and down matrices of
each (layer, expert) pair given a row, and each row's input and output,
at the served type's bytes), over the summed device self time of the
decode step's `moe_experts` ops in the trace."""
from pathlib import Path

from bench import work
from bench.loader import load_module

OP = "jit_decode_step:moe_experts"

_rows = load_module(Path(__file__).with_name("moe_rows_per_expert.py"))


def read(r):
    if r.trace is None or r.peaks is None \
            or not hasattr(r.shape, "expert_work"):
        return None
    spent = r.trace.op_seconds.get(OP, 0.0)
    routed = _rows.decode_routing(r)
    if spent <= 0 or routed is None:
        return None
    flops, nbytes = r.shape.expert_work(*routed, r.q_bytes)
    least, _ = work.least_time(flops, nbytes, r.peaks.flops_bf16,
                               r.peaks.hbm_bytes)
    return 100.0 * least / spent
