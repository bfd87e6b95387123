"""A configuration file as the program and the reference take it, and the
weights made from the seed.

The weights are made by the benchmark, not by the program, so that the
reference can make the same ones without taking anything from the program.
They are made on the device in one jitted call, in the type they are
served in, and laid out as the program's parameter tree for the dense
family: embedding/tok (V, d) [and embedding/unembed (d, V) when the
embeddings are not tied], layers/* stacked over the layer axis,
final_norm (d,).
"""
from __future__ import annotations

import json
from functools import partial
from pathlib import Path

import numpy as np

from bench.work import Shape

EMBED_STD = 0.02
NORM_STD = 0.05


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def head_dim(spec: dict) -> int:
    return int(spec.get("head_dim")
               or spec["hidden_size"] // spec["num_attention_heads"])


def shape(spec: dict) -> Shape:
    return Shape(num_layers=spec["num_hidden_layers"],
                 d_model=spec["hidden_size"],
                 num_heads=spec["num_attention_heads"],
                 num_kv_heads=spec["num_key_value_heads"],
                 head_dim=head_dim(spec), d_ff=spec["intermediate_size"],
                 vocab_size=spec["vocab_size"])


def model_config(spec: dict):
    """The program's ModelConfig for this file (dense family)."""
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


def seed_key(seed: int, stream: str):
    """A JAX key for one use of `seed` (weights, ...), for any whole seed
    however large."""
    import jax
    words = np.random.SeedSequence(
        [*seed_words(seed), *stream.encode()]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def seed_words(seed: int) -> list:
    """A whole seed of any size or sign as non-negative 32-bit words."""
    sign, seed = (1, -seed) if seed < 0 else (0, seed)
    words = [sign]
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def _weights(key, s: Shape, tied: bool, qk_norm: bool, dtype):
    import jax
    import jax.numpy as jnp
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


def make_weights(spec: dict, seed: int, device):
    """The weights of `spec` from `seed`, on `device`, in one jitted
    call."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    fn = jax.jit(partial(_weights, s=shape(spec),
                         tied=bool(spec["tie_word_embeddings"]),
                         qk_norm=bool(spec["qk_norm"]),
                         dtype=jnp.dtype(spec["torch_dtype"])),
                 out_shardings=SingleDeviceSharding(device))
    with jax.default_device(device):
        key = seed_key(seed, "weights")
    return jax.block_until_ready(fn(key))


def reference_module(root: Path, spec: dict):
    """The plain reference named by the configuration file."""
    from bench.loader import load_module
    return load_module(root / "bench" / "reference" / f"{spec['reference']}.py")
