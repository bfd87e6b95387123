"""Unified model API: one entry point per (family × step kind).

Every architecture exposes the same four callables through this module:

  init_params(cfg, key, abstract)      -> (params, logical_axes)
  loss_fn(params, cfg, batch)          -> (loss, metrics)     [train]
  prefill_fn(params, cfg, batch)       -> (logits, cache)     [serving]
  decode_fn(params, cfg, tokens, cache, pos) -> (logits, cache)

plus `input_specs(cfg, shape)` producing ShapeDtypeStruct stand-ins for the
multi-pod dry-run (weak-type-correct, shardable, zero allocation).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.models import common as cm
from repro.models import griffin, mamba2, moe, transformer, whisper

_MODULES = {
    "dense": transformer,
    "vlm": transformer,
    "moe": moe,
    "hybrid": griffin,
    "ssm": mamba2,
    "audio": whisper,
}


def module_for(cfg: ModelConfig):
    return _MODULES[cfg.family]


def init_params(cfg: ModelConfig, key=None, abstract: bool = False):
    """Returns (params, axes). With abstract=True params are SDS leaves and
    no key is needed."""
    if key is None:
        key = jax.random.key(0)
    return cm.unzip(module_for(cfg).init(key, cfg, abstract=abstract))


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

def loss_fn(params, cfg: ModelConfig, batch):
    """batch: tokens, labels, loss_mask?, frames?, patch_embeds?."""
    tokens, labels = batch["tokens"], batch["labels"]
    aux = jnp.float32(0.0)
    if cfg.family == "moe":
        logits, aux = moe.forward_train(params, cfg, tokens)
    elif cfg.family == "audio":
        logits = whisper.forward_train(params, cfg, tokens, batch["frames"])
    elif cfg.family == "vlm":
        logits = transformer.forward_train(params, cfg, tokens,
                                           patch_embeds=batch["patch_embeds"])
    else:
        logits = module_for(cfg).forward_train(params, cfg, tokens)
    mask = batch.get("loss_mask")
    if cfg.family == "vlm" and mask is None:
        # patch positions carry no next-token target
        t = tokens.shape[1]
        mask = (jnp.arange(t)[None, :] >= cfg.num_patches).astype(jnp.float32)
        mask = jnp.broadcast_to(mask, tokens.shape)
    loss = cm.cross_entropy(logits, labels, mask)
    metrics = {"loss": loss, "aux_loss": aux}
    return loss + aux, metrics


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def prefill_fn(params, cfg: ModelConfig, batch, backend: str = "ref"):
    """`backend` runs the moe family's expert kernel (kernels/moe_experts);
    the other families have no kernel in prefill."""
    tokens = batch["tokens"]
    if cfg.family == "audio":
        return whisper.prefill(params, cfg, tokens, batch["frames"])
    if cfg.family == "vlm":
        return transformer.prefill(params, cfg, tokens,
                                   patch_embeds=batch["patch_embeds"])
    if cfg.family == "moe":
        return moe.prefill(params, cfg, tokens, backend=backend)
    return module_for(cfg).prefill(params, cfg, tokens)


def decode_fn(params, cfg: ModelConfig, tokens, cache, pos):
    return module_for(cfg).decode_step(params, cfg, tokens, cache, pos)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return module_for(cfg).init_cache(cfg, batch, max_len, dtype)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return module_for(cfg).cache_specs(cfg, batch, max_len, dtype)


def pad_cache(cfg: ModelConfig, cache, max_len: int):
    """Grow a prefill-sized dense KV cache to max_len (dense families only).
    State caches (ssm/hybrid) are fixed-size already."""
    if cfg.family in ("ssm", "hybrid"):
        return cache

    def pad(x, key):
        if key in ("ck", "cv"):  # cross-attn caches never grow
            return x
        t = x.shape[2]
        if t >= max_len:
            return x[:, :, :max_len]
        pad_width = [(0, 0)] * x.ndim
        pad_width[2] = (0, max_len - t)
        return jnp.pad(x, pad_width)

    return {k: pad(v, k) for k, v in cache.items()}


# --------------------------------------------------------------------------
# dry-run input specs
# --------------------------------------------------------------------------

def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, t = shape.global_batch, shape.seq_len
    act = jnp.dtype(cfg.param_dtype)
    if shape.kind == "train":
        batch = {"tokens": _i32(b, t), "labels": _i32(b, t)}
        if cfg.family == "audio":
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq_len, cfg.frontend_dim), act)
        if cfg.family == "vlm":
            batch["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.num_patches, cfg.frontend_dim), act)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": _i32(b, t)}
        if cfg.family == "audio":
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder_seq_len, cfg.frontend_dim), act)
        if cfg.family == "vlm":
            batch["patch_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.num_patches, cfg.frontend_dim), act)
        return batch
    # decode: one new token against a seq_len-deep cache
    return {
        "tokens": _i32(b),
        "pos": _i32(b),
        "cache": cache_specs(cfg, b, t),
    }


def supports_cell(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether this (arch × shape) cell runs; reason when skipped."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("full-attention arch: 500k-token decode needs "
                       "sub-quadratic attention (DESIGN.md notes the skip)")
    return True, ""
