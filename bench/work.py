"""Work the served model needs, counted from shapes: model FLOPs per
prefill and decode token, and the paged-attention kernel's FLOPs and bytes
from each row's real context.

Counts are of the algorithm, not of what the program happens to execute:
a matmul of (m, k) by (k, n) is 2mkn FLOPs; causal attention of a query at
position p reads p + 1 keys. Padding rows, padded pages and recomputed
chunks are not work.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Shape:
    """The attention and vocabulary sizes of a decoder that the counts
    need. Each model family (bench/families/<family>.py) extends it with
    the sizes of its feed-forward part and counts that part's work in
    `ffn_flops`."""
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    vocab_size: int

    def ffn_flops(self) -> int:
        """Weight-matmul FLOPs of one layer's feed-forward part for one
        token."""
        raise NotImplementedError(f"{type(self).__name__} counts no "
                                  f"feed-forward work")


def layer_matmul_flops(s: Shape) -> int:
    """Weight-matmul FLOPs of one layer for one token: q, k, v and output
    projections and the family's feed-forward part."""
    qo = 2 * 2 * s.d_model * s.num_heads * s.head_dim
    kv = 2 * 2 * s.d_model * s.num_kv_heads * s.head_dim
    return qo + kv + s.ffn_flops()


def attention_flops(s: Shape, context: int) -> int:
    """Score and value FLOPs of one query token over `context` keys, all
    layers: q.k and p.v, each 2 * head_dim per key per query head."""
    return s.num_layers * 4 * s.num_heads * s.head_dim * context


def unembed_flops(s: Shape) -> int:
    return 2 * s.d_model * s.vocab_size


def prefill_flops(s: Shape, prompt_len: int) -> int:
    """One whole prompt: every token through every layer, causal attention
    over its prefix, and logits for the last position only (the one that
    yields the first output token)."""
    t = prompt_len
    causal_keys = t * (t + 1) // 2
    return (t * s.num_layers * layer_matmul_flops(s)
            + s.num_layers * 4 * s.num_heads * s.head_dim * causal_keys
            + unembed_flops(s))


def decode_flops(s: Shape, context: int) -> int:
    """One decoded token whose query attends to `context` keys (its own
    included)."""
    return (s.num_layers * layer_matmul_flops(s) + attention_flops(s, context)
            + unembed_flops(s))


def paged_attention_work(s: Shape, contexts, kv_bytes: int,
                         q_bytes: int) -> tuple[int, int]:
    """(FLOPs, bytes) the paged-attention kernel needs for one decode step
    over all layers: each live row reads its q and writes its output
    (num_heads * head_dim each), and reads K and V of its real context
    (num_kv_heads * head_dim per token each). Pages past a row's context
    and padding rows are not needed."""
    flops = nbytes = 0
    for ctx in contexts:
        flops += attention_flops(s, ctx)
        nbytes += s.num_layers * (
            2 * s.num_heads * s.head_dim * q_bytes
            + 2 * ctx * s.num_kv_heads * s.head_dim * kv_bytes)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bytes: float) -> tuple[float, str]:
    """Roofline: the larger of compute time and memory time, and which of
    the two bounds it."""
    tc, tm = flops / peak_flops, nbytes / peak_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")
