"""Mixture-of-Experts decoder transformer (qwen3-moe, kimi-k2).

Training routes with sort-based capacity dispatch (MegaBlocks-lite):
tokens are sorted by expert id, placed into an (E, C, d) buffer and
processed with a dense blocked einsum against stacked expert weights, the
buffer being the natural unit for expert-parallel sharding over the
`model` mesh axis. Capacity factor 1.25; entries past an expert's
capacity fall back to the shared expert path (or zero for pure-routed
models) exactly like capacity-dropping GShard routers.

Serving (`capacity_factor=None`) drops nothing: the (token, expert)
entries of the live rows are sorted by expert and a grouped matmul
(`kernels/moe_experts`: the Pallas kernel, or XLA's ragged dot) computes
each expert over exactly the rows routed to it, however skewed the
routing.

Serving (`prefill` here, and the paged decode step in
`engine/paged_model.py`) computes in float32 over the weights as stored
(`SERVE_DTYPE`, `serving_precision`): the residual stream and every
activation, and each matmul at full precision. The router's top-k is a
step function of the residual. In bfloat16, rounding swaps a token's
near-tied last chosen and first unchosen experts in a few percent of
(token, layer) pairs at qwen3-moe's widths, each swap moves the token's
residual by a few percent of its norm, and the served tokens stray from
the published model's as far as int8 weights take them. Training, and the
slot-cache `decode_step` that only the dry-run lowers, keep the
parameters' type.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import ModelConfig
from repro.kernels.moe_experts import ops as moe_ops
from repro.models import common as cm
from repro.models import transformer as tfm

CAPACITY_FACTOR = 1.25
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
SERVE_DTYPE = jnp.float32


def serving_precision():
    """The matmul precision of serving, a context around its trace."""
    return jax.default_matmul_precision("float32")


def _init_moe_block(ini: cm.Initializer, cfg: ModelConfig):
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    p = {
        "router": ini.dense((d, e), ("embed", "expert"), scale=0.1),
        "w_gate": ini.dense((e, d, f), ("expert", "embed", "mlp"), fan_in=d),
        "w_up": ini.dense((e, d, f), ("expert", "embed", "mlp"), fan_in=d),
        "w_down": ini.dense((e, f, d), ("expert", "mlp", "embed"), fan_in=f),
    }
    if cfg.num_shared_experts:
        p["shared"] = cm.init_mlp(ini, d, cfg.moe_d_ff * cfg.num_shared_experts)
    return p


def _init_layer(key, cfg: ModelConfig, abstract: bool = False):
    ini = cm.Initializer(key, jnp.dtype(cfg.param_dtype), abstract)
    return {
        "attn": cm.init_attention(ini, cfg),
        "moe": _init_moe_block(ini, cfg),
        "ln1": ini.ones((cfg.d_model,), ("embed",)),
        "ln2": ini.ones((cfg.d_model,), ("embed",)),
    }


def init(key, cfg: ModelConfig, abstract: bool = False):
    k_emb, k_layers = jax.random.split(key, 2)
    ini = cm.Initializer(k_emb, jnp.dtype(cfg.param_dtype), abstract)
    return {
        "embedding": cm.init_embedding(ini, cfg),
        "layers": tfm.stacked_layer_init(k_layers, cfg, _init_layer, abstract),
        "final_norm": ini.ones((cfg.d_model,), ("embed",)),
    }


# --------------------------------------------------------------------------
# routing + dispatch
# --------------------------------------------------------------------------

def moe_block(p, cfg: ModelConfig, x, capacity_factor=CAPACITY_FACTOR):
    """x: (B, T, d) -> (y, aux_loss) in training, the capacity-1.25
    GShard router.

    capacity_factor=None -> serving: (y, group_sizes (E,) int32), dropless
    (`serve_experts`).
    """
    if capacity_factor is None:
        return serve_experts(p, cfg, x)
    b, t, d = x.shape
    n = b * t
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(n, d)

    logits = (xf @ p["router"]).astype(jnp.float32)          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, k)                        # (N, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renormalise

    # ---- sort-based dispatch into (E, C, d) ----
    cap = int(max(1, (n * k * capacity_factor) // e))
    flat_e = top_i.reshape(-1)                                # (N*k,)

    # load-balancing aux loss (Switch-style), via scatter-add (no N×E one-hot)
    counts = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0)
    router_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(counts / (n * k) * router_prob) * cfg.router_aux_loss_coef
    flat_w = top_p.reshape(-1).astype(x.dtype)
    flat_tok = jnp.repeat(jnp.arange(n), k)

    order = jnp.argsort(flat_e)                               # stable
    se, sw, stok = flat_e[order], flat_w[order], flat_tok[order]
    # position of each entry within its expert's run
    start = jnp.searchsorted(se, jnp.arange(e), side="left")  # (E,)
    pos = jnp.arange(n * k) - start[se]
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, e * cap)           # overflow slot

    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(xf[stok])
    buf = buf[:-1].reshape(e, cap, d)
    buf = cm.act_shard(buf, "expert", None, None)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    out = cm.act_shard(out, "expert", None, None)

    out_flat = out.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], out_flat[jnp.minimum(slot, e * cap - 1)],
                         0.0) * sw[:, None]
    y = jnp.zeros((n, d), x.dtype).at[stok].add(gathered)

    if "shared" in p:
        y = y + cm.mlp(p["shared"], xf)
    return y.reshape(b, t, d), aux


def serve_experts(p, cfg: ModelConfig, x, *, backend: str = "ref",
                  routed=None, layer=None):
    """Dropless routed experts. x: (B, T, d) -> (y (B, T, d), group_sizes
    (E,) int32, the live rows routed to each expert). The expert kernel
    runs on `backend`; with `layer`, p's expert weights are stacked over
    the layers (`split_experts`) and that layer's are read in place.

    The router's logits accumulate in float32; softmax, the top
    `num_experts_per_tok`, renormalised to sum to 1. The rows of the
    flattened batch that `routed` (n,) bool marks (all without it) are
    routed; the others, a decode batch's padding, go to no expert and come
    out 0. The live (row, expert) entries are sorted by expert and the
    grouped matmul computes each expert's gated SiLU MLP over its own rows
    only; each row's k outputs are gathered back and summed with their
    weights.
    """
    b, t, d = x.shape
    n = b * t
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    xf = x.reshape(n, d)
    logits = jnp.dot(xf, p["router"], preferred_element_type=jnp.float32)
    top_p, top_i = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if routed is not None:   # padding rows to expert e, past every group
        top_i = jnp.where(routed[:, None], top_i, e)
    flat_e = top_i.reshape(-1)                                # (N*k,)
    order = jnp.argsort(flat_e)                               # stable
    group_sizes = jnp.zeros((e,), jnp.int32).at[flat_e].add(1, mode="drop")
    out = moe_ops.moe_experts(xf[order // k], p["w_gate"], p["w_up"],
                              p["w_down"], group_sizes, layer,
                              backend=backend)
    unsort = jnp.zeros_like(order).at[order].set(jnp.arange(n * k))
    y = jnp.einsum("nk,nkd->nd", top_p, out[unsort].reshape(n, k, d))
    if "shared" in p:
        y = y + cm.mlp(p["shared"], xf)
    return y.astype(x.dtype).reshape(b, t, d), group_sizes


def split_experts(layers):
    """Split the stacked layer weights for a serving layer scan: (the
    layers without the routed experts' matrices, plus `layer`, each
    layer's index; the experts' matrices, stacked over layers). The scan
    body hands the kernel the whole stack and its layer's index, since a
    per-layer slice, an operand of the kernel, would be copied first."""
    moe = dict(layers["moe"])
    experts = {w: moe.pop(w) for w in EXPERT_WEIGHTS}
    n = jax.tree.leaves(layers)[0].shape[0]
    return dict(layers, moe=moe, layer=jnp.arange(n)), experts


# --------------------------------------------------------------------------
# forward / serving
# --------------------------------------------------------------------------

def _block(lp, cfg: ModelConfig, x, positions, capacity_factor=CAPACITY_FACTOR):
    h = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
    x = x + cm.attention_train(lp["attn"], cfg, h, positions=positions)
    h = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
    y, aux = moe_block(lp["moe"], cfg, h, capacity_factor)
    if capacity_factor is None:     # serving: group sizes, no balance loss
        aux = jnp.zeros((), jnp.float32)
    return x + y, aux


def forward_train(params, cfg: ModelConfig, tokens, remat: bool = True,
                  capacity_factor=CAPACITY_FACTOR):
    x = cm.embed(params["embedding"], tokens)
    x = cm.act_shard(x, "batch", None, None)
    t = x.shape[1]
    positions = jnp.arange(t)[None, :]

    def body(carry, lp):
        x = carry
        x, aux = _block(lp, cfg, x, positions, capacity_factor)
        return x, aux

    body_fn = jax.checkpoint(body) if remat else body
    x, auxes = cm.layer_scan(body_fn, x, params["layers"])
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return cm.unembed(params["embedding"], x), jnp.sum(auxes)


init_cache = tfm.init_cache
cache_specs = tfm.cache_specs


def prefill(params, cfg: ModelConfig, tokens, backend: str = "ref"):
    x = cm.embed(params["embedding"], tokens).astype(SERVE_DTYPE)
    x = cm.act_shard(x, "batch", None, None)
    layers, experts = split_experts(params["layers"])

    def body(x, lp):
        h = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, k, v = cm.attention_prefill(lp["attn"], cfg, h)
        x = x + a
        h = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, _ = serve_experts(dict(lp["moe"], **experts), cfg, h,
                             backend=backend, layer=lp["layer"])
        return x + y, {"k": k, "v": v}

    with serving_precision():
        x, cache = cm.layer_scan(body, x, layers)
        x = cm.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        return cm.unembed(params["embedding"], x)[:, 0], cache


def decode_step(params, cfg: ModelConfig, tokens, cache, pos):
    x = cm.embed(params["embedding"], tokens[:, None])
    x = cm.act_shard(x, "batch", None, None)

    def body(x, inp):
        lp, ck, cv = inp
        h = cm.rms_norm(x, lp["ln1"], cfg.norm_eps)
        a, ck, cv = cm.attention_decode(lp["attn"], cfg, h, ck, cv, pos)
        x = x + a
        h = cm.rms_norm(x, lp["ln2"], cfg.norm_eps)
        y, _ = moe_block(lp["moe"], cfg, h, capacity_factor=None)
        return x + y, {"k": ck, "v": cv}

    x, cache = cm.layer_scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = cm.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return cm.unembed(params["embedding"], x)[:, 0], cache
