"""Kernel microbenchmarks: paged-attention decode + flash prefill.

On this CPU container we measure the jnp reference path's wall time (XLA:CPU)
for regression tracking, and derive the TPU-side roofline estimate for the
Pallas kernel from its exact FLOP/byte counts (the kernel itself is
validated in interpret mode by tests/test_kernels.py)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import TPU_V5E
from repro.kernels.flash_prefill.ref import flash_prefill_ref
from repro.kernels.paged_attention.ref import paged_attention_ref


def _wall(fn, *args, iters=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def paged_attention_bench(s=16, h=16, kv=8, d=128, bs=32, mb=64):
    nb = s * mb + 1
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    pk = jnp.asarray(rng.normal(size=(nb, kv, bs, d)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(nb, kv, bs, d)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, nb, size=(s, mb)), jnp.int32)
    lens = jnp.full((s,), mb * bs, jnp.int32)
    f = jax.jit(paged_attention_ref)
    wall = _wall(f, q, pk, pv, bt, lens)
    # TPU roofline: decode attention is HBM-bound on KV reads
    kv_bytes = 2 * s * mb * bs * kv * d * 2          # bf16 on TPU
    flops = 2 * 2 * s * h * d * mb * bs
    t_mem = kv_bytes / TPU_V5E.hbm_bandwidth
    t_flop = flops / TPU_V5E.peak_flops_bf16
    return {
        "name": "paged_attention_decode",
        "cpu_ref_wall_us": wall * 1e6,
        "tpu_roofline_us": max(t_mem, t_flop) * 1e6,
        "bound": "memory" if t_mem > t_flop else "compute",
        "kv_bytes": kv_bytes,
    }


def flash_prefill_bench(b=1, t=4096, h=16, kv=8, d=128):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, t, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kv, d)), jnp.float32)
    f = jax.jit(flash_prefill_ref)
    wall = _wall(f, q, k, v)
    flops = 2 * 2 * b * h * d * t * t / 2            # causal triangle
    t_flop = flops / TPU_V5E.peak_flops_bf16
    io_bytes = 2 * (b * t * (h + 2 * kv) * d) * 2
    t_mem = io_bytes / TPU_V5E.hbm_bandwidth
    return {
        "name": "flash_prefill_causal",
        "cpu_ref_wall_us": wall * 1e6,
        "tpu_roofline_us": max(t_flop, t_mem) * 1e6,
        "bound": "compute" if t_flop > t_mem else "memory",
        "flops": flops,
    }


def run():
    return [paged_attention_bench(), flash_prefill_bench()]
