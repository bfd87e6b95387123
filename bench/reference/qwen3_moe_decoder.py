"""Plain reference of a mixture-of-experts decoder (Qwen3-MoE): float32,
matmuls at `highest` precision, no cache, no kernels, no batching.

It follows the published modelling code: token embedding; per layer
RMSNorm, q/k/v projections (with an RMSNorm over head_dim on q and k where
the configuration has qk_norm, as Qwen3 does), rotary embedding of the
rotate-half kind, causal softmax attention with grouped KV heads (query
head h reads KV head h // (heads / kv_heads)), output projection and
residual; RMSNorm, the routed experts and residual: a softmax over the
router's logits, the `num_experts_per_tok` best renormalised to sum to 1,
every expert's gated SiLU MLP computed for every token and the chosen
ones summed with those weights (the weights, 0 for the experts not chosen,
are applied before the down projection's sum over experts, so no
(tokens, experts, hidden) array is held); final RMSNorm and the
unembedding (the embedding transposed where tied). It imports nothing of
the program. It reads the configuration file's keys and a weight tree laid
out as the `qwen3_moe` family (bench/families/qwen3_moe.py) makes it. One
layer's weights are cast to float32 at a time, inside the layer scan: at
128 experts of width 768 over d 2048 that is 2.4 GB, where all six layers
at once would be 17 GB.

`control=True` is the same arithmetic with every matrix rounded to int8,
symmetric, one scale per output channel: the step below the bfloat16 that
the configuration states. It is the control of the output check and never
runs in a timed run.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _int8(w, contract_axes):
    """Round `w` to int8 with one scale per output channel (the max over
    the contracted axes), back in float32."""
    amax = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (T, H, D), position t at row t."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _experts(h, m, top_k):
    """The routed experts of (T, d) inputs `h`: (T, d)."""
    probs = jax.nn.softmax(
        jnp.einsum("td,de->te", h, m["router"], precision=HI), axis=-1)
    top_p, top_i = lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    weight = jnp.zeros_like(probs).at[rows, top_i].set(top_p)     # (T, E)
    g = jnp.einsum("td,edf->tef", h, m["w_gate"], precision=HI)
    u = jnp.einsum("td,edf->tef", h, m["w_up"], precision=HI)
    hidden = weight[:, :, None] * jax.nn.silu(g) * u
    return jnp.einsum("tef,efd->td", hidden, m["w_down"], precision=HI)


def _layer(x, lw, *, heads, kv_heads, top_k, eps, theta, qk_norm, control):
    f32 = {k: jax.tree.map(lambda a: a.astype(jnp.float32), v)
           for k, v in lw.items()}
    a, m = f32["attn"], dict(f32["moe"])
    wq, wk, wv, wo = a["wq"], a["wk"], a["wv"], a["wo"]
    if control:
        wq, wk, wv = (_int8(w, (0,)) for w in (wq, wk, wv))
        wo = _int8(wo, (0, 1))
        m["router"] = _int8(m["router"], (0,))
        for w in ("w_gate", "w_up", "w_down"):
            m[w] = _int8(m[w], (1,))
    t = x.shape[0]
    h = _rms(x, f32["ln1"], eps)
    q = jnp.einsum("td,dhk->thk", h, wq, precision=HI)
    k = jnp.einsum("td,dhk->thk", h, wk, precision=HI)
    v = jnp.einsum("td,dhk->thk", h, wv, precision=HI)
    if qk_norm:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=HI)
    x = x + jnp.einsum("qhd,hde->qe", o, wo, precision=HI)
    h = _rms(x, f32["ln2"], eps)
    x = x + _experts(h, m, top_k)
    return x, None


@partial(jax.jit, static_argnames=("heads", "kv_heads", "top_k", "eps",
                                   "theta", "qk_norm", "tied", "control"))
def _logits(weights, tokens, *, heads, kv_heads, top_k, eps, theta, qk_norm,
            tied, control):
    tok = weights["embedding"]["tok"].astype(jnp.float32)
    unembed = (tok.T if tied
               else weights["embedding"]["unembed"].astype(jnp.float32))
    if control:
        tok = _int8(tok, (1,))
        unembed = tok.T if tied else _int8(unembed, (0,))
    x = tok[tokens]
    body = partial(_layer, heads=heads, kv_heads=kv_heads, top_k=top_k,
                   eps=eps, theta=theta, qk_norm=qk_norm, control=control)
    x, _ = lax.scan(body, x, weights["layers"])
    x = _rms(x, weights["final_norm"].astype(jnp.float32), eps)
    return jnp.einsum("td,dv->tv", x, unembed, precision=HI)


def logits(weights, spec: dict, tokens, control: bool = False):
    """(T, V) float32 logits at every position of one sequence `tokens`
    (T,), layer by layer."""
    return _logits(weights, tokens, heads=spec["num_attention_heads"],
                   kv_heads=spec["num_key_value_heads"],
                   top_k=spec["num_experts_per_tok"],
                   eps=float(spec["rms_norm_eps"]),
                   theta=float(spec["rope_theta"]),
                   qk_norm=bool(spec["qk_norm"]),
                   tied=bool(spec["tie_word_embeddings"]), control=control)
