"""A mixture-of-experts decoder family, laid out as `repro.models.moe`
takes it (Qwen3-MoE): the attention of the dense family, and in place of
its MLP a softmax router over `num_experts` experts, of which the
`num_experts_per_tok` best, renormalised, each run a gated SiLU MLP of
width `moe_intermediate_size`.

Weights: embedding/tok (V, d) [and embedding/unembed (d, V) when the
embeddings are not tied], layers/* stacked over the layer axis (attention
wq, wk, wv, wo [q_norm, k_norm]; moe router (d, E), w_gate and w_up
(E, d, f), w_down (E, f, d); ln1, ln2), final_norm (d,).
"""
from __future__ import annotations

from dataclasses import dataclass

from bench.model import head_dim
from bench.work import Shape

EMBED_STD = 0.02
NORM_STD = 0.05


@dataclass(frozen=True)
class MoeShape(Shape):
    """A routed decoder: the experts, those each token uses, and their
    width."""
    num_experts: int
    experts_per_tok: int
    moe_d_ff: int

    def ffn_flops(self) -> int:
        """The router, and the gated MLPs of the experts a token uses;
        the experts it is not routed to are no work."""
        router = 2 * self.d_model * self.num_experts
        return router + self.experts_per_tok * 3 * 2 * self.d_model \
            * self.moe_d_ff


def shape(spec: dict) -> MoeShape:
    return MoeShape(num_layers=spec["num_hidden_layers"],
                    d_model=spec["hidden_size"],
                    num_heads=spec["num_attention_heads"],
                    num_kv_heads=spec["num_key_value_heads"],
                    head_dim=head_dim(spec), vocab_size=spec["vocab_size"],
                    num_experts=spec["num_experts"],
                    experts_per_tok=spec["num_experts_per_tok"],
                    moe_d_ff=spec["moe_intermediate_size"])


def model_config(spec: dict):
    from repro.config import ModelConfig
    return ModelConfig(
        name=spec["name"], family="moe",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        d_ff=spec["moe_intermediate_size"], vocab_size=spec["vocab_size"],
        head_dim=head_dim(spec), qk_norm=bool(spec["qk_norm"]),
        rope_theta=float(spec["rope_theta"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        norm_eps=float(spec["rms_norm_eps"]),
        max_position_embeddings=spec["max_position_embeddings"],
        num_experts=spec["num_experts"],
        num_experts_per_tok=spec["num_experts_per_tok"],
        moe_d_ff=spec["moe_intermediate_size"],
        param_dtype=spec["torch_dtype"])


def weights(key, spec: dict):
    import jax
    import jax.numpy as jnp
    s = shape(spec)
    dtype = jnp.dtype(spec["torch_dtype"])
    keys = iter(jax.random.split(key, 16))
    L, d, h, kv, hd, v = (s.num_layers, s.d_model, s.num_heads,
                          s.num_kv_heads, s.head_dim, s.vocab_size)
    e, f = s.num_experts, s.moe_d_ff

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, dtype) * std

    def norm(shape):
        return 1 + jax.random.normal(next(keys), shape, dtype) * NORM_STD

    attn = {"wq": normal((L, d, h, hd), d ** -0.5),
            "wk": normal((L, d, kv, hd), d ** -0.5),
            "wv": normal((L, d, kv, hd), d ** -0.5),
            "wo": normal((L, h, hd, d), (h * hd) ** -0.5)}
    if spec["qk_norm"]:
        attn["q_norm"] = norm((L, hd))
        attn["k_norm"] = norm((L, hd))
    params = {
        "embedding": {"tok": normal((v, d), EMBED_STD)},
        "layers": {
            "attn": attn,
            "moe": {"router": normal((L, d, e), d ** -0.5),
                    "w_gate": normal((L, e, d, f), d ** -0.5),
                    "w_up": normal((L, e, d, f), d ** -0.5),
                    "w_down": normal((L, e, f, d), f ** -0.5)},
            "ln1": norm((L, d)), "ln2": norm((L, d))},
        "final_norm": norm((d,)),
    }
    if not spec["tie_word_embeddings"]:
        params["embedding"]["unembed"] = normal((d, v), d ** -0.5)
    return params
