"""Compile the Pallas kernels of the served path for a described TPU v5e.

Interpret mode (tests/test_kernels.py, tests/test_moe_serving.py) cannot
see Mosaic's tiling rules or its VMEM limit; the TPU compiler, installed
here, compiles for a chip that is described and not attached. Attention
widths are qwen3-1.7b's: 16 query heads, 8 KV heads, head_dim 128, with
the served pool (512 blocks, 1024-token sequences); the routed experts
are qwen3-moe-30b-a3b's: 128 of width 768 over d 2048, top-8. Nothing
runs, so nothing here says anything about results or time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_prefill.kernel import flash_prefill
from repro.kernels.moe_experts.kernel import moe_experts
from repro.kernels.paged_attention.kernel import paged_attention

CFG = configs.get("qwen3-1.7b")
MOE = configs.get("qwen3-moe-30b-a3b")
NUM_BLOCKS, MAX_MODEL_LEN, MAX_SEQS = 512, 1024, 8


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2 host, with the persistent cache
    off: a compile for a described chip is written there but cannot be read
    back without one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("block_size", [16, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_compiles_for_v5e(one_chip, block_size, dtype):
    h, kv, d = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    pool = _sds((NUM_BLOCKS, kv, block_size, d), dtype, one_chip)
    args = (_sds((MAX_SEQS, h, d), dtype, one_chip), pool, pool,
            _sds((MAX_SEQS, MAX_MODEL_LEN // block_size), jnp.int32,
                 one_chip),
            _sds((MAX_SEQS,), jnp.int32, one_chip))
    compiled = paged_attention.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_compiles_for_v5e(one_chip, dtype):
    t = 2048
    q = _sds((1, t, CFG.num_heads, CFG.head_dim), dtype, one_chip)
    kv = _sds((1, t, CFG.num_kv_heads, CFG.head_dim), dtype, one_chip)
    compiled = flash_prefill.lower(q, kv, kv, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_names_survive_an_enclosing_program(one_chip):
    """The device trace keys a kernel's time by its HLO name, and
    `paged_attn_roofline` reads `…:paged_attention`: the name comes from
    the kernel's own `name=`, so it holds when a jitted step inlines the
    kernel's body (without `name=` XLA calls it `closed_call`)."""
    import re
    bs, h, kv, d = 16, CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    pool = _sds((NUM_BLOCKS, kv, bs, d), jnp.float32, one_chip)
    args = (_sds((MAX_SEQS, h, d), jnp.float32, one_chip), pool, pool,
            _sds((MAX_SEQS, MAX_MODEL_LEN // bs), jnp.int32, one_chip),
            _sds((MAX_SEQS,), jnp.int32, one_chip))

    def step(q, pk, pv, bt, lens):
        return paged_attention.__wrapped__(q, pk, pv, bt, lens,
                                           interpret=False) * 2.0

    text = jax.jit(step).lower(*args).compile().as_text()
    assert re.search(r"%paged_attention(\.\d+)? = \S+ custom-call", text)
    t = 512
    q = _sds((1, t, h, d), jnp.bfloat16, one_chip)
    k = _sds((1, t, kv, d), jnp.bfloat16, one_chip)
    text = jax.jit(lambda q, k, v: flash_prefill.__wrapped__(
        q, k, v, interpret=False) + 1).lower(q, k, k).compile().as_text()
    assert re.search(r"%flash_prefill(\.\d+)? = \S+ custom-call", text)


# rows: a decode batch of 32 rows x top-8, and a 1000-token prompt's; in
# float32 as the moe family serves them, and in the weights' bf16
@pytest.mark.parametrize("rows", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m", [32 * 8, 1024 * 8])
def test_moe_experts_compiles_for_v5e(one_chip, m, rows):
    """Gate and up blocks of 2048 x 768 in bf16 fit the kernel's VMEM, one
    layer of six is read from the stacked weights, and both calls keep the
    name `moe_experts_roofline` reads inside an enclosing program."""
    import re
    layers, e, d, f = 6, MOE.num_experts, MOE.d_model, MOE.moe_d_ff
    w_in = _sds((layers, e, d, f), jnp.bfloat16, one_chip)
    args = (_sds((m, d), rows, one_chip), w_in, w_in,
            _sds((layers, e, f, d), jnp.bfloat16, one_chip),
            _sds((e,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))
    compiled = jax.jit(lambda *a: moe_experts.__wrapped__(
        *a, tm=128, interpret=False) * 2.0).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"%moe_experts(\.\d+)? = \S+ custom-call",
                          text)) == 2
    # the stacked weights are read in place: no layer of them is copied
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * m * d * 4
