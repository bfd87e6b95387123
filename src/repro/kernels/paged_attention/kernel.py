"""Pallas TPU paged-attention decode kernel.

TPU adaptation of vLLM's PagedAttention: instead of GPU pointer-chasing
gathers, the block table is *scalar-prefetched* and drives each step's
BlockSpec index_map, so the needed KV blocks are DMA'd HBM->VMEM as dense
(block_size, head_dim) tiles that keep the MXU/VPU fed. The pool is
head-major, (NB, KV, BS, D), so a tile's last two dims are whole
(block_size, head_dim) slabs, as Mosaic's (8, 128) tiling requires.

Grid: (seqs, kv_heads, num_pages). The page axis is `arbitrary` (sequential)
so a flash-style running softmax accumulates in VMEM scratch; pages past
context_len are skipped via pl.when (their DMAs read block 0, which is the
reserved null block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(block_tables_ref, lens_ref,       # scalar prefetch
            q_ref, k_ref, v_ref,              # VMEM inputs
            o_ref,                            # VMEM output
            m_ref, l_ref, acc_ref,            # VMEM scratch
            *, bs: int, pages: int):
    s = pl.program_id(0)
    page = pl.program_id(2)

    @pl.when(page == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = lens_ref[s]

    @pl.when(page * bs < ctx)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (QPK, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (BS, D)
        v = v_ref[0, 0].astype(jnp.float32)
        scale = q.shape[-1] ** -0.5
        qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        token_idx = page * bs + jax.lax.broadcasted_iota(jnp.int32,
                                                         qk.shape, 1)
        qk = jnp.where(token_idx < ctx, qk, NEG_INF)  # (QPK, BS)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(qk, axis=-1, keepdims=True)   # (QPK, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(qk - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(page == pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("interpret",))
def paged_attention(q, pool_k, pool_v, block_tables, context_lens,
                    *, interpret: bool = False):
    """q: (S, H, D); pool_k/v: (NB, KV, BS, D); block_tables: (S, MB);
    context_lens: (S,). Returns (S, H, D).

    interpret=True runs the kernel body in Python on the CPU (the
    validation mode of the CPU tests); the default compiles it for the TPU.
    """
    s, h, d = q.shape
    nb, kv, bs, _ = pool_k.shape
    mb = block_tables.shape[1]
    qpk = h // kv
    qg = q.reshape(s, kv, qpk, d)

    grid = (s, kv, mb)

    def q_map(si, hi, pi, bt, lens):
        return (si, hi, 0, 0)

    def kv_map(si, hi, pi, bt, lens):
        return (bt[si, pi], hi, 0, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, pages=mb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, qpk, d), q_map),
                pl.BlockSpec((1, 1, bs, d), kv_map),
                pl.BlockSpec((1, 1, bs, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, qpk, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((qpk, 1), jnp.float32),
                pltpu.VMEM((qpk, 1), jnp.float32),
                pltpu.VMEM((qpk, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s, kv, qpk, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",      # the op's name in the device trace
    )(block_tables, context_lens, qg, pool_k, pool_v)
    return out.reshape(s, h, d)
