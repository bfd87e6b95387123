"""bench/work.py and bench/peaks.py against hand counts at a tiny dense
shape (bench/families/dense.py): 2 layers, d_model 8, 2 query heads and
1 KV head of 4, d_ff 16, vocab 32.
"""
import pytest

from bench import loader, peaks, work

DenseShape = loader.family_module(loader.ROOT, {}).DenseShape
S = DenseShape(num_layers=2, d_model=8, num_heads=2, num_kv_heads=1,
               head_dim=4, d_ff=16, vocab_size=32)


def test_layer_matmuls():
    # q and o: 2*8*(2*4) each; k and v: 2*8*(1*4) each; gate, up, down:
    # 2*8*16 each
    assert work.layer_matmul_flops(S) == 128 + 128 + 64 + 64 + 3 * 256


def test_decode_token():
    # 2 layers of matmuls, attention over 5 keys (2 layers * 2 heads *
    # 4 * (2 + 2) FLOPs per key), unembedding 2*8*32
    assert work.attention_flops(S, 5) == 320
    assert work.decode_flops(S, 5) == 2 * 1152 + 320 + 512


def test_prefill_prompt():
    # 3 tokens through 2 layers; causal keys 1 + 2 + 3; one unembedding
    assert work.prefill_flops(S, 3) == 3 * 2 * 1152 + 2 * 4 * 2 * 4 * 6 + 512


def test_paged_attention_counts_real_context_only():
    # rows of context 5 and 3; per layer a row moves q and out (2*4 values
    # each, 2 bytes) and K and V of its context (1*4 values per token
    # each, 4 bytes)
    flops, nbytes = work.paged_attention_work(S, [5, 3], kv_bytes=4,
                                              q_bytes=2)
    assert flops == 320 + 192
    assert nbytes == 2 * (32 + 2 * 5 * 16) + 2 * (32 + 2 * 3 * 16)


def test_a_shape_without_a_family_counts_no_feed_forward_work():
    s = work.Shape(num_layers=2, d_model=8, num_heads=2, num_kv_heads=1,
                   head_dim=4, vocab_size=32)
    with pytest.raises(NotImplementedError, match="feed-forward"):
        work.decode_flops(s, 5)


def test_least_time_names_its_bound():
    assert work.least_time(2e12, 1e9, 1e12, 1e9) == (2.0, "compute")
    assert work.least_time(1e12, 4e9, 1e12, 1e9) == (4.0, "memory")


def test_peaks_by_device_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bytes) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no peaks"):
        peaks.peaks_for("TPU v9 imaginary")
