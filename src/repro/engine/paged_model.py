"""Model glue for decoding against the paged KV pool.

Supports the dense / vlm / moe families (the ones with a KV cache the paper
technique applies to). Decode runs one token per active slot against the
pool via the paged-attention kernel, whose backend the caller names (the
compiled Pallas kernel on a TPU; `ref` or `interpret` on the CPU).
SSM/hybrid/audio families are served through the dense state executor
instead.

The pool is head-major, (L, NB, KV, BS, D), the layout the Pallas kernel
tiles.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.kernels.paged_attention import ops as pa_ops
from repro.models import common as cm
from repro.models import moe as moe_mod


def init_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
              dtype=jnp.float32, device=None):
    shape = (cfg.num_layers, num_blocks, cfg.num_kv_heads, block_size,
             cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype, device=device),
            "v": jnp.zeros(shape, dtype, device=device)}


@functools.partial(jax.jit, static_argnames=("block_size",))
def write_prefill(pool, cache, block_table, block_size: int):
    """Scatter one sequence's dense prefill cache into its pool blocks.

    cache: {"k": (L, 1, T, KV, D)}; block_table: (nb,) int32 where
    nb = ceil(T / block_size). T is padded up to a whole block.
    """
    def scatter(pool_x, cache_x):
        l, one, t, kvh, d = cache_x.shape
        nb = block_table.shape[0]
        pad = nb * block_size - t
        c = jnp.pad(cache_x[:, 0], ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = c.reshape(l, nb, block_size, kvh, d).transpose(0, 1, 3, 2, 4)
        return pool_x.at[:, block_table].set(c.astype(pool_x.dtype))

    return {
        "k": scatter(pool["k"], cache["k"]),
        "v": scatter(pool["v"], cache["v"]),
    }


def decode_step(params, cfg: ModelConfig, tokens, pos, pool, block_tables,
                *, backend: str):
    """tokens/pos: (S,); pool as init_pool; block_tables: (S, MB).
    Returns (logits (S, V), new pool), and for the moe family a third
    item, the per-layer group sizes (L, E) int32 of the live rows: the rows
    past position 0, since a sequence decodes only after its prompt, and
    the rows that pad a batch sit at 0. The moe family computes in
    float32 (`moe.SERVE_DTYPE`, `moe.serving_precision`), its logits
    float32. The kernels run on `backend`."""
    x = cm.embed(params["embedding"], tokens[:, None])   # (S, 1, d)
    s = tokens.shape[0]
    bs = pool["k"].shape[3]
    blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    ctx = pos + 1
    layers, experts = params["layers"], None
    numerics = contextlib.nullcontext()
    if cfg.family == "moe":     # served in float32 (models/moe.py)
        layers, experts = moe_mod.split_experts(layers)
        x = x.astype(moe_mod.SERVE_DTYPE)
        numerics = moe_mod.serving_precision()

    def body(x, inp):
        lp, pk, pv = inp
        h = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = cm._qkv(lp["attn"], cfg, h, pos[:, None])
        # (NB, KV, BS, D) indexed [blk, :, off] -> (S, KV, D)
        pk = pk.at[blk, :, off].set(k[:, 0].astype(pk.dtype))
        pv = pv.at[blk, :, off].set(v[:, 0].astype(pv.dtype))
        a = pa_ops.paged_attention(q[:, 0], pk, pv, block_tables, ctx,
                                   backend=backend)
        x = x + jnp.einsum("shd,hdo->so", a, lp["attn"]["wo"])[:, None]
        h = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, groups = moe_mod.serve_experts(
                dict(lp["moe"], **experts), cfg, h, backend=backend,
                routed=pos > 0, layer=lp["layer"])
            return x + y, {"k": pk, "v": pv, "groups": groups}
        x = x + cm.mlp(lp["mlp"], h)
        return x, {"k": pk, "v": pv}

    with numerics:
        x, pool = lax.scan(body, x, (layers, pool["k"], pool["v"]))
        x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = cm.unembed(params["embedding"], x)[:, 0]
    if cfg.family == "moe":
        return logits, {"k": pool["k"], "v": pool["v"]}, pool["groups"]
    return logits, pool
