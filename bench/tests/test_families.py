"""The dense family behind `bench/families/dense.py` makes what the
benchmark made before families were files of their own: the same weight
tree from the same seed, leaf for leaf and byte for byte, and the same
work counts of both published dense shapes. The digests and integers
below were taken with the code that preceded the move. A configuration's
`deployment` sets the engine's decode rows and KV blocks, and refuses
anything else it does not honour."""
import hashlib

import jax
import numpy as np
import pytest

from bench import loader, model, work
from bench.harness import deployment
from bench.tests import tiny


def _digest(tree) -> str:
    """SHA-256 over every leaf's path, dtype, shape and bytes, in the
    tree's order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape};"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


UNTIED = dict(name="tiny-untied", qk_norm=False, tie_word_embeddings=False)


@pytest.mark.parametrize("over, seed, digest", [
    ({}, 0,
     "ce4f92497172dcb6a7e659957b5b6cfee7ebce3de440258d1c05721761346af5"),
    ({}, 2**31 + 7,
     "5ec21307da6b379ef1509a21a0465ab49d7be74254f5dda22e1a004bf8a2acfc"),
    (UNTIED, 0,
     "73cdf0ccda032dbb927486706db7e85e3035e7a57e2f1cdc32002a0800acf3f8"),
    (UNTIED, 2**31 + 7,
     "f238c29784d4f2d837b9ab85569f4129ff06e70a1b00f729cf58157cacdcd326"),
])
def test_dense_weights_are_those_made_before(over, seed, digest):
    w = model.make_weights(tiny.tiny_spec(**over), seed, jax.devices()[0])
    assert {str(leaf.dtype) for leaf in jax.tree.leaves(w)} == {"bfloat16"}
    assert _digest(w) == digest


# (layer matmuls, decode at context 1000, prefill of 777 tokens, paged
# attention over contexts 1, 533 and 1024 at 4-byte KV and 2-byte q)
@pytest.mark.parametrize("config, counts", [
    ("qwen3-1.7b", (100663296, 3670278144, 2259982581760,
                    (357367808, 358055936))),
    ("phi3-mini-3.8b", (226492416, 4495048704, 3234773458944,
                        (344604672, 689872896))),
])
def test_dense_counts_are_those_made_before(config, counts):
    spec = model.load(loader.ROOT / "bench" / "configs" / f"{config}.json")
    assert "family" not in spec
    s = model.shape(spec)
    assert (work.layer_matmul_flops(s), work.decode_flops(s, 1000),
            work.prefill_flops(s, 777),
            work.paged_attention_work(s, [1, 533, 1024], 4, 2)) == counts
    assert model.model_config(spec).family == "dense"


@pytest.mark.parametrize("dep, sizes", [
    (None, (8, 512)),
    ({"routing_policy": "least_loaded"}, (8, 512)),
    ({"max_num_seqs": 4}, (4, 512)),
    ({"max_num_seqs": 16, "num_blocks": 1024}, (16, 1024)),
])
def test_deployment_sets_engine_sizes(dep, sizes):
    spec = tiny.tiny_spec()
    spec.pop("deployment")
    if dep is not None:
        spec["deployment"] = dep
    d = deployment(spec)
    assert (d["max_num_seqs"], d["num_blocks"]) == sizes


@pytest.mark.parametrize("dep, match", [
    ({"replicas_per_chip": 2}, "not honoured"),
    ({"block_size": 32}, "not honoured"),
    ({"max_num_seqs": 0}, "at least 1"),
    ({"num_blocks": 2.5}, "whole number"),
    ({"max_num_seqs": True}, "whole number"),
])
def test_deployment_refuses_what_it_does_not_honour(dep, match):
    with pytest.raises(ValueError, match=match):
        deployment(tiny.tiny_spec(deployment=dep))
