"""The dense decoder family (Qwen3, Phi-3): the program's configuration of
a file, its weights drawn from a key, and the sizes its work counts need.

The weights are laid out as the program's parameter tree for the dense
family: embedding/tok (V, d) [and embedding/unembed (d, V) when the
embeddings are not tied], layers/* stacked over the layer axis (attention
wq, wk, wv, wo [q_norm, k_norm]; the gated MLP w_gate, w_up, w_down; ln1,
ln2), final_norm (d,).
"""
from __future__ import annotations

from dataclasses import dataclass

from bench.model import head_dim
from bench.work import Shape

EMBED_STD = 0.02
NORM_STD = 0.05


@dataclass(frozen=True)
class DenseShape(Shape):
    """A dense decoder: the gated MLP's width besides."""
    d_ff: int

    def ffn_flops(self) -> int:
        """The gated MLP: gate, up and down projections."""
        return 3 * 2 * self.d_model * self.d_ff


def shape(spec: dict) -> DenseShape:
    return DenseShape(num_layers=spec["num_hidden_layers"],
                      d_model=spec["hidden_size"],
                      num_heads=spec["num_attention_heads"],
                      num_kv_heads=spec["num_key_value_heads"],
                      head_dim=head_dim(spec), d_ff=spec["intermediate_size"],
                      vocab_size=spec["vocab_size"])


def model_config(spec: dict):
    """The program's ModelConfig for this file."""
    from repro.config import ModelConfig
    return ModelConfig(
        name=spec["name"], family="dense",
        num_layers=spec["num_hidden_layers"], d_model=spec["hidden_size"],
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        head_dim=head_dim(spec), qk_norm=bool(spec["qk_norm"]),
        rope_theta=float(spec["rope_theta"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        norm_eps=float(spec["rms_norm_eps"]),
        max_position_embeddings=spec["max_position_embeddings"],
        param_dtype=spec["torch_dtype"])


def weights(key, spec: dict):
    """The weight tree of `spec` drawn from `key`, traced under one jit."""
    import jax
    import jax.numpy as jnp
    s = shape(spec)
    tied, qk_norm = bool(spec["tie_word_embeddings"]), bool(spec["qk_norm"])
    dtype = jnp.dtype(spec["torch_dtype"])
    keys = iter(jax.random.split(key, 16))
    L, d, h, kv, hd, ff, v = (s.num_layers, s.d_model, s.num_heads,
                              s.num_kv_heads, s.head_dim, s.d_ff,
                              s.vocab_size)

    # drawn in the served type, so no float32 copy of a leaf is held
    def normal(shape, std):
        return jax.random.normal(next(keys), shape, dtype) * std

    def norm(shape):
        return 1 + jax.random.normal(next(keys), shape, dtype) * NORM_STD

    attn = {"wq": normal((L, d, h, hd), d ** -0.5),
            "wk": normal((L, d, kv, hd), d ** -0.5),
            "wv": normal((L, d, kv, hd), d ** -0.5),
            "wo": normal((L, h, hd, d), (h * hd) ** -0.5)}
    if qk_norm:
        attn["q_norm"] = norm((L, hd))
        attn["k_norm"] = norm((L, hd))
    params = {
        "embedding": {"tok": normal((v, d), EMBED_STD)},
        "layers": {
            "attn": attn,
            "mlp": {"w_gate": normal((L, d, ff), d ** -0.5),
                    "w_up": normal((L, d, ff), d ** -0.5),
                    "w_down": normal((L, ff, d), ff ** -0.5)},
            "ln1": norm((L, d)), "ln2": norm((L, d))},
        "final_norm": norm((d,)),
    }
    if not tied:
        params["embedding"]["unembed"] = normal((d, v), d ** -0.5)
    return params
