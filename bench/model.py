"""A configuration file as the program and the reference take it, and the
weights made from the seed.

The weights are made by the benchmark, not by the program, so that the
reference can make the same ones without taking anything from the program.
They are made on the device in one jitted call, in the type they are
served in. What depends on the model family (the program's configuration,
the weight tree and the sizes the work counts need) is in the module that
the file's `family` names, bench/families/<family>.py, "dense" where the
file names none.
"""
from __future__ import annotations

import json
from functools import partial
from pathlib import Path

import numpy as np

from bench.loader import ROOT, family_module, load_module


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def head_dim(spec: dict) -> int:
    return int(spec.get("head_dim")
               or spec["hidden_size"] // spec["num_attention_heads"])


def shape(spec: dict, root: Path = ROOT):
    """The sizes the work counts (bench/work.py) take, as a
    `bench.work.Shape` of the file's family."""
    return family_module(root, spec).shape(spec)


def model_config(spec: dict, root: Path = ROOT):
    """The program's ModelConfig for this file."""
    return family_module(root, spec).model_config(spec)


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


def make_weights(spec: dict, seed: int, device, root: Path = ROOT):
    """The weights of `spec` from `seed`, on `device`, in one jitted
    call."""
    import jax
    from jax.sharding import SingleDeviceSharding
    fn = jax.jit(partial(family_module(root, spec).weights, spec=spec),
                 out_shardings=SingleDeviceSharding(device))
    with jax.default_device(device):
        key = seed_key(seed, "weights")
    return jax.block_until_ready(fn(key))


def reference_module(root: Path, spec: dict):
    """The plain reference named by the configuration file."""
    return load_module(root / "bench" / "reference" / f"{spec['reference']}.py")
