"""Pallas TPU flash-attention prefill kernel (causal, optional local window).

Grid: (B, KV_heads, num_q_blocks, num_k_blocks), k-block axis sequential
('arbitrary') with flash running-softmax scratch in VMEM. Causality is
exploited structurally: k-blocks entirely above the diagonal (and, with a
window, entirely below it) are skipped with pl.when, so the kernel does
~half (or O(window/T)) of the quadratic work — this is the chunked-VMEM
adaptation of the paper's prefill hot loop.

Block shapes default to (128, head_dim) q-tiles × (512, head_dim) k-tiles,
(8,128)-aligned for the MXU. Operands are head-major inside the kernel,
(B, KV, T*QPK, D) for q and (B, KV, T, D) for k/v, so each tile's last two
dims are whole (rows, head_dim) slabs, as Mosaic's tiling requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bq: int, bk: int, nk: int, window: int, qpk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    causal_live = k_start <= q_start + bq - 1          # some pair in range
    window_live = (window == 0) or (k_start + bk > q_start - window + 1)

    @pl.when(causal_live & window_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq*qpk, D) flattened
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        d = q.shape[-1]
        scale = d ** -0.5
        qk = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        # row r of qk corresponds to query position q_start + r // qpk
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, qk.shape, 0) // qpk
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, qk.shape, 1)
        mask = cols <= rows
        if window:
            mask &= cols > rows - window
        qk = jnp.where(mask, qk, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_cur = jnp.max(qk, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(qk - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk",
                                             "interpret"))
def flash_prefill(q, k, v, window: int = 0, bq: int = 128, bk: int = 512,
                  interpret: bool = False):
    """q: (B, T, H, D); k/v: (B, T, KV, D) -> (B, T, H, D)."""
    b, t, h, d = q.shape
    kvh = k.shape[2]
    qpk = h // kvh
    bq = min(bq, t)
    bk = min(bk, t)
    assert t % bq == 0 and t % bk == 0, (t, bq, bk)
    nq, nk = t // bq, t // bk

    # group q rows by kv head, (B, T, KV, QPK, D) -> (B, KV, T*QPK, D): one
    # (bq*qpk, d) flat tile per (b, kv) so the MXU sees one tall matmul
    qg = q.reshape(b, t, kvh, qpk, d).transpose(0, 2, 1, 3, 4) \
          .reshape(b, kvh, t * qpk, d)
    kg = k.transpose(0, 2, 1, 3)                        # (B, KV, T, D)
    vg = v.transpose(0, 2, 1, 3)

    grid = (b, kvh, nq, nk)

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, nk=nk, window=window,
                          qpk=qpk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq * qpk, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq * qpk, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, t * qpk, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * qpk, 1), jnp.float32),
            pltpu.VMEM((bq * qpk, 1), jnp.float32),
            pltpu.VMEM((bq * qpk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_prefill",      # the op's name in the device trace
    )(qg, kg, vg)

    return out.reshape(b, kvh, t, qpk, d).transpose(0, 2, 1, 3, 4) \
              .reshape(b, t, h, d)
