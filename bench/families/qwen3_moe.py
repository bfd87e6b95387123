"""The Qwen3-MoE family (Qwen3-30B-A3B), laid out as `repro.models.moe`
takes it: the attention of the dense family, and in place of its MLP a
softmax router over `num_experts` experts, of which the
`num_experts_per_tok` best, renormalised (`norm_topk_prob`), each run a
gated SiLU MLP of width `moe_intermediate_size`. Every layer is sparse
(`decoder_sparse_step` 1, no `mlp_only_layers`) and there is no shared
expert; a file that says otherwise is refused, since the program serves
no such layer.

The program serves this family in float32 over its bfloat16 weights
(repro.models.moe), so a routed row moves float32 in and out of the expert
kernel. `model_config` refuses a program whose serving expert layer drops
routed rows: such a layer serves wrong tokens, and the one that dropped
them stalled the chip on this family's cell inside the window (PERF.md),
where a refusal ends the run at once.

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
ROW_BYTES = 4            # a routed row, in and out of the expert kernel


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

    def expert_work(self, rows: int, active: int,
                    nbytes: int) -> tuple[int, int]:
        """(FLOPs, bytes) the routed experts' grouped matmul needs for
        `rows` routed (token, expert) rows and `active` (layer, expert)
        pairs with at least one row, both summed over layers: gate, up and
        down of width `moe_d_ff` for each row; the three matrices of each
        active pair read once, at `nbytes` a number, and each row's input
        and output (d each) moved once, at `ROW_BYTES`. The hidden
        activation between the two matmuls, padding rows and experts with
        no row are not work."""
        d, f = self.d_model, self.moe_d_ff
        return (rows * 3 * 2 * d * f,
                active * 3 * d * f * nbytes + rows * 2 * d * ROW_BYTES)


def shape(spec: dict) -> MoeShape:
    for key, served in (("norm_topk_prob", True), ("decoder_sparse_step", 1),
                        ("mlp_only_layers", []),
                        ("shared_expert_intermediate_size", 0)):
        if spec.get(key, served) != served:
            raise ValueError(f"{spec['name']}: {key} {spec[key]!r} is not "
                             f"served; the moe family serves {served!r}")
    return MoeShape(num_layers=spec["num_hidden_layers"],
                    d_model=spec["hidden_size"],
                    num_heads=spec["num_attention_heads"],
                    num_kv_heads=spec["num_key_value_heads"],
                    head_dim=head_dim(spec), vocab_size=spec["vocab_size"],
                    num_experts=spec["num_experts"],
                    experts_per_tok=spec["num_experts_per_tok"],
                    moe_d_ff=spec["moe_intermediate_size"])


def refuse_a_dropping_expert_layer():
    """Exit unless the program's serving expert layer
    (`moe_block(..., capacity_factor=None)`) gives each of 128 equal
    tokens, all routed to one of 16 experts, the same output: a layer with
    a capacity per expert leaves the rows past it at 0."""
    import jax.numpy as jnp
    from repro.config import ModelConfig
    from repro.models import moe
    e, d, n = 16, 8, 128
    cfg = ModelConfig(name="dropless-probe", family="moe", num_layers=1,
                      d_model=d, num_heads=1, num_kv_heads=1, d_ff=d,
                      vocab_size=8, num_experts=e, num_experts_per_tok=1,
                      moe_d_ff=d)
    w = jnp.ones((e, d, d), jnp.float32) / d
    p = {"router": jnp.zeros((d, e), jnp.float32).at[:, 0].set(1.0),
         "w_gate": w, "w_up": w, "w_down": w}
    y = moe.moe_block(p, cfg, jnp.ones((1, n, d), jnp.float32),
                      capacity_factor=None)[0]
    if not bool(jnp.all(y == y[0, 0])):
        raise SystemExit("the program's serving expert layer drops routed "
                         "rows: it cannot serve this family")


def model_config(spec: dict):
    """The program's ModelConfig for this file, once the program's
    serving expert layer has shown that it drops no routed row."""
    from repro.config import ModelConfig
    refuse_a_dropping_expert_layer()
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
