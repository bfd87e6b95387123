"""Pure-jnp oracle for the paged-attention decode kernel.

Layouts (TPU-native):
  q            : (S, H, D)          one new token per sequence
  pool_k/v     : (NB, KV, BS, D)    global block pool, head-major
  block_tables : (S, MB) int32      logical page -> physical block
  context_lens : (S,)   int32       tokens valid per sequence (incl. new)

GQA is handled by grouping H = KV * QPK query heads per kv head.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_attention_ref(q, pool_k, pool_v, block_tables, context_lens):
    s, h, d = q.shape
    nb, kv, bs, _ = pool_k.shape
    mb = block_tables.shape[1]
    qpk = h // kv

    def gather(pool):                    # (S, MB, KV, BS, D) -> (S, KV, MB*BS, D)
        x = pool[block_tables].transpose(0, 2, 1, 3, 4)
        return x.reshape(s, kv, mb * bs, d).astype(jnp.float32)

    kg, vg = gather(pool_k), gather(pool_v)
    qg = q.reshape(s, kv, qpk, d).astype(jnp.float32)

    logits = jnp.einsum("skqd,sktd->skqt", qg, kg) * (d ** -0.5)
    valid = (jnp.arange(mb * bs)[None, :] < context_lens[:, None])
    logits = jnp.where(valid[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("skqt,sktd->skqd", probs, vg)
    return out.reshape(s, h, d).astype(q.dtype)
